"""Per-kernel tests: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes and dtypes per the deliverable contract."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels import ops, ref
from repro.kernels import q8_matmul as q8
from repro.kernels.flash_attention import flash_attention
from repro.kernels.q3k_matmul import q3k_matmul
from repro.kernels.q4_matmul import q4_matmul
from repro.kernels.q8_matmul import q8_matmul, q8_matmul_w8a8
from repro.kernels.tiling import k_block


def _xw(m, k, n, seed=0, dtype=jnp.float32):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (n, k), dtype) * 0.05
    return x, w


# K that is not a multiple of the 512 K tile (SD-Turbo's 640, 768 and
# 1280): a kernel that read a partial last K block returned NaN here.
ODD_K = [(16, 640, 128), (8, 768, 64), (8, 1280, 136)]


# Shapes whose ``q8_plan`` tiles differ from a single step: M not a
# multiple of its tile (77, 308, 600), N a whole dim not divisible by
# 128 (320, 640), K whole (640, 1280) or split into blocks (2048, 5120),
# and several M tiles reusing one dequantized weight panel (308, 600,
# 1200), with the K split on top (600, 1200).
PLANNED = [(77, 640, 320), (308, 1280, 640), (600, 2048, 640),
           (308, 5120, 1280), (1200, 5120, 256)]


@pytest.mark.parametrize("m,k,n", [(8, 64, 16), (32, 256, 64),
                                   (128, 1024, 256), (17, 512, 96)]
                         + ODD_K + PLANNED)
def test_q8_dequant_kernel_matches_oracle(m, k, n):
    x, w = _xw(m, k, n, seed=m)
    wq = quant.quantize_q8_0(w)
    want = ref.q8_matmul_ref(x, wq)
    got = q8_matmul(x, wq.qs, wq.d.astype(jnp.float32), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("m,k,n", ODD_K)   # small K: test_extensions.py
def test_q4_kernel_matches_oracle(m, k, n):
    x, w = _xw(m, k, n, seed=m + 3)
    wq = quant.quantize_q4_0(w)
    want = ref.q4_matmul_ref(x, wq)
    got = q4_matmul(x, wq.qs, wq.d.astype(jnp.float32), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("k,pref,align,want", [
    (320, 512, 256, 320), (640, 512, 256, 640), (768, 512, 256, 256),
    (1280, 512, 256, 256), (5120, 512, 256, 512), (768, 1024, 1024, 768),
    (2304, 1024, 1024, 2304), (5120, 1024, 1024, 1024)])
def test_k_block_divides_k(k, pref, align, want):
    bk = k_block(k, pref, align)
    assert bk == want and k % bk == 0
    assert bk == k or (bk % align == 0 and bk <= pref)


# The Q8_0 sites of one UNet evaluation at batch 4, (m, n, k): count
# (``bench/configs/*_cost.matmul_calls``), with the grid steps of the old
# (M/128, N/128, K/512-or-256) tiling.
SDXL_UNET_Q8 = {(308, 640, 2048): 20, (308, 1280, 2048): 120,
                (4096, 1280, 1280): 372, (4096, 1280, 5120): 60,
                (4096, 10240, 1280): 60, (16384, 640, 640): 70,
                (16384, 640, 2560): 10, (16384, 5120, 640): 10}
SD15_UNET_Q8 = {(256, 1280, 1280): 6, (256, 1280, 5120): 1,
                (256, 10240, 1280): 1, (308, 320, 768): 10,
                (308, 640, 768): 10, (308, 1280, 768): 12,
                (1024, 1280, 1280): 30, (1024, 1280, 5120): 5,
                (1024, 10240, 1280): 5, (4096, 640, 640): 30,
                (4096, 640, 2560): 5, (4096, 5120, 640): 5,
                (16384, 320, 320): 30, (16384, 320, 1280): 5,
                (16384, 2560, 320): 5}
# CLIP (both SDXL encoders, the pooled projection) and VAE sites.
OTHER_Q8 = [(4, 1280, 1280), (308, 768, 768), (308, 768, 3072),
            (308, 3072, 768), (308, 1280, 1280), (308, 1280, 5120),
            (308, 5120, 1280), (16384, 512, 512), (16384, 1536, 512)]
# Decode- and prefill-sized LM projections (8B-class widths).
LM_Q8 = [(m, n, k) for m in (1, 8, 32, 64)
         for n, k in ((4096, 4096), (14336, 4096), (4096, 14336))]


def _q8_steps(m, n, k, bm, bn, bk):
    return -(-m // bm) * -(-n // bn) * (k // bk)


@pytest.mark.parametrize("sites,old", [(SDXL_UNET_Q8, 1_698_800),
                                       (SD15_UNET_Q8, 84_520)],
                         ids=["sdxl", "sd15"])
def test_q8_plan_steps_per_unet_evaluation(sites, old):
    """The planner's engagement count: grid steps per UNet evaluation
    fall to under 2 % of the old tiling's."""
    def steps(plan):
        return sum(_q8_steps(*s, *plan(*s)) * c for s, c in sites.items())

    assert steps(lambda m, n, k: (min(128, m), min(128, n),
                                  k_block(k, 512, q8.K_ALIGN))) == old
    assert steps(q8.q8_plan) <= old // 50


@pytest.mark.parametrize("m,n,k", sorted({*SDXL_UNET_Q8, *SD15_UNET_Q8,
                                          *OTHER_Q8, *LM_Q8}))
def test_q8_plan_fits_vmem_with_legal_tiles(m, n, k):
    bm, bn, bk = q8.q8_plan(m, n, k)
    assert q8.q8_vmem_bytes(bm, bn, bk, k) <= q8.VMEM_BUDGET
    assert bm == m or (bm % 16 == 0 and bm <= q8.BM_MAX)
    assert bn == n or bn % 128 == 0
    assert k % bk == 0 and (bk == k or bk % q8.K_ALIGN == 0)


@pytest.mark.parametrize("m,n,k", LM_Q8)
def test_q8_plan_keeps_decode_in_one_m_tile(m, n, k):
    assert q8.q8_plan(m, n, k)[0] == m


def _pallas_grid(fn, *args):
    """The grid and the block index maps of ``fn``'s one pallas_call."""
    jx = jax.make_jaxpr(functools.partial(fn, interpret=True))(*args)
    gm, = [e.params["grid_mapping"] for e in jx.jaxpr.eqns
           if e.primitive.name == "pallas_call"]
    return gm.grid, [functools.partial(jax.core.eval_jaxpr,
                                       b.index_map_jaxpr.jaxpr,
                                       b.index_map_jaxpr.consts)
                     for b in gm.block_mappings]


@pytest.mark.parametrize("m,k,n", PLANNED + [(16384, 640, 640)])
def test_q8_weight_blocks_are_copied_once(m, k, n):
    """The grid follows ``q8_plan`` and, walked in order, changes the
    weight and scale block indices once per (N tile, K tile): the
    pipeline copies the packed weights from HBM once per call."""
    S = jax.ShapeDtypeStruct
    grid, maps = _pallas_grid(
        q8_matmul, S((m, k), jnp.bfloat16), S((n, k), jnp.int8),
        S((n, k // 32), jnp.float32))
    bm, bn, bk = q8.q8_plan(m, n, k)
    assert grid == (-(-n // bn), -(-m // bm), k // bk)
    for idx in maps[1:3]:
        seen = [tuple(int(v) for v in idx(*p))
                for p in np.ndindex(*grid)]
        fetches = 1 + sum(a != b for a, b in zip(seen, seen[1:]))
        assert fetches == grid[0] * grid[2]


@pytest.mark.parametrize("kernel,grid", [
    ("q4_0", (2, 3, 5)), ("q3_k", (2, 3, 2)), ("w8a8", (2, 3, 5))])
def test_other_quantized_kernels_keep_their_tiles(kernel, grid):
    """The planner is the Q8_0 dequant kernel's alone: the Q4_0, Q3_K
    and w8a8 kernels keep 128 x 128 tiles and ``k_block``'s K tile."""
    S = jax.ShapeDtypeStruct
    bf, i8, u8, f32 = jnp.bfloat16, jnp.int8, jnp.uint8, jnp.float32
    m, k, n = 256, 2048 if kernel == "q3_k" else 1280, 320
    fn, args = {
        "q4_0": (q4_matmul, (S((m, k), bf), S((n, k // 2), u8),
                             S((n, k // 32), f32))),
        "q3_k": (q3k_matmul, (S((m, k), bf), S((n, k // 4), u8),
                              S((n, k // 8), u8), S((n, k // 16), u8),
                              S((n, k // 256), f32))),
        "w8a8": (q8_matmul_w8a8, (S((m, k), i8), S((m, k // 32), f32),
                                  S((n, k), i8), S((n, k // 32), f32))),
    }[kernel]
    assert _pallas_grid(fn, *args)[0] == grid


@pytest.mark.parametrize("m,k,n", [(8, 64, 16), (64, 512, 128),
                                   (8, 640, 32)])
def test_q8_w8a8_kernel_matches_oracle(m, k, n):
    x, w = _xw(m, k, n, seed=m + 1)
    wq = quant.quantize_q8_0(w)
    xa = quant.quantize_q8_0(x)
    xs = xa.d.astype(jnp.float32)
    want = ref.q8_matmul_w8a8_ref(xa.qs, xs, wq)
    got = q8_matmul_w8a8(xa.qs, xs, wq.qs, wq.d.astype(jnp.float32),
                         interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("m,k,n", [(8, 256, 16), (32, 1024, 64),
                                   (8, 768, 32), (8, 1280, 16),
                                   (8, 2304, 16), (8, 3072, 16)])
@pytest.mark.parametrize("scale_bits", [6, 5])
def test_q3k_kernel_matches_oracle(m, k, n, scale_bits):
    x, w = _xw(m, k, n, seed=m + 2)
    wq = quant.quantize_q3_k(w, scale_bits=scale_bits)
    want = ref.q3k_matmul_ref(x, wq)
    sc = quant.unpack_scales6(wq.scales).reshape(n, -1)
    got = q3k_matmul(x, wq.ql, wq.qh, sc, wq.d.astype(jnp.float32),
                     interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None)])
def test_flash_attention_matches_oracle(dtype, causal, window):
    b, h, s, d = 2, 4, 256, 32
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(kq, (b, h, s, d), dtype) * 0.5
    k = jax.random.normal(kk, (b, h, s, d), dtype) * 0.5
    v = jax.random.normal(kv, (b, h, s, d), dtype)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2 if dtype == jnp.bfloat16 else 2e-5, rtol=1e-2)


def test_flash_attention_cross_lengths():
    """Sq != Sk (decode-style suffix attention)."""
    b, h, sq, sk, d = 1, 2, 64, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (b, h, sq, d)) * 0.3
    k = jax.random.normal(ks[1], (b, h, sk, d)) * 0.3
    v = jax.random.normal(ks[2], (b, h, sk, d))
    want = ref.flash_attention_ref(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


def test_chunked_attention_matches_ref():
    b, h, s, d = 1, 2, 512, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, h, s, d)) * 0.4
    k = jax.random.normal(ks[1], (b, h, s, d)) * 0.4
    v = jax.random.normal(ks[2], (b, h, s, d))
    want = ref.flash_attention_ref(q, k, v, causal=True)
    got = ops._chunked_attention(q, k, v, causal=True, window=None,
                                 scale=d ** -0.5, q_chunk=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("fmt", ["q8_0", "q4_0"])
def test_quantized_matmul_ragged_k_runs_the_kernel(fmt):
    """A tail-padded (``logical``) tensor goes through the kernel with
    zero-padded activations, not a silent fallback to the oracle."""
    x, w = _xw(5, 100, 48, seed=4)
    wq = quant.quantize(w, fmt)
    assert wq.logical == 100
    want = (ref.q8_matmul_ref if fmt == "q8_0" else ref.q4_matmul_ref)(x, wq)
    got = ops.quantized_matmul(x, wq, force="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


def test_quantized_matmul_dispatch_gqa_and_leading_dims():
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 3, 128), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(9), (64, 128)) * 0.1
    wq = quant.quantize_q8_0(w)
    y = ops.quantized_matmul(x, wq)
    assert y.shape == (2, 3, 64) and y.dtype == jnp.bfloat16
    # GQA fold in ops.attention
    q = jax.random.normal(jax.random.PRNGKey(10), (1, 8, 16, 32))
    k = jax.random.normal(jax.random.PRNGKey(11), (1, 2, 16, 32))
    v = jax.random.normal(jax.random.PRNGKey(12), (1, 2, 16, 32))
    out = ops.attention(q, k, v)
    assert out.shape == q.shape


# ------------------------------------------------------------ kernel names

def _kernel_cases():
    from repro.kernels.flash_decode import flash_decode, flash_decode_paged
    from repro.kernels.flash_prefill import (flash_prefill_paged,
                                             flash_prefill_paged_q8)
    S = jax.ShapeDtypeStruct
    bf, i8, u8 = jnp.bfloat16, jnp.int8, jnp.uint8
    f32, i32, f16 = jnp.float32, jnp.int32, jnp.float16
    m, k, n = 128, 256, 128
    t, h, g, d, bs, nb, mb = 16, 2, 2, 64, 16, 8, 4
    pool = (S((nb, h, bs, d), bf),) * 2
    return {
        "q8_matmul": (q8_matmul, (S((m, k), bf), S((n, k), i8),
                                  S((n, k // 32), f32))),
        "q8_matmul_w8a8": (q8_matmul_w8a8, (
            S((m, k), i8), S((m, k // 32), f32), S((n, k), i8),
            S((n, k // 32), f32))),
        "q3k_matmul": (q3k_matmul, (
            S((m, k), bf), S((n, k // 4), u8), S((n, k // 8), u8),
            S((n, k // 16), u8), S((n, k // 256), f32))),
        "q4_matmul": (q4_matmul, (S((m, k), bf), S((n, k // 2), u8),
                                  S((n, k // 32), f32))),
        "flash_attention": (flash_attention, (S((1, 2, 128, 64), bf),) * 3),
        "flash_decode": (flash_decode, (
            S((2, h, g, d), bf), S((2, h, 128, d), bf),
            S((2, h, 128, d), bf), S((2,), i32))),
        "flash_decode_paged": (flash_decode_paged, (
            S((2, h, g, d), bf), *pool, S((2, mb), i32), S((2,), i32))),
        "flash_prefill_paged": (flash_prefill_paged, (
            S((t, h, g, d), bf), S((t, h, d), bf), S((t, h, d), bf), *pool,
            S((mb,), i32), S((), i32))),
        "flash_prefill_paged_q8": (flash_prefill_paged_q8, (
            S((t, h, g, d), bf), S((t, h, d), bf), S((t, h, d), bf),
            S((nb, h, bs, d), i8), S((nb, h, bs, d), i8),
            S((nb, h, bs, d // 32), f16), S((nb, h, bs, d // 32), f16),
            S((mb,), i32), S((), i32))),
    }


KERNELS = ["q8_matmul", "q8_matmul_w8a8", "q3k_matmul", "q4_matmul",
           "flash_attention", "flash_decode", "flash_decode_paged",
           "flash_prefill_paged", "flash_prefill_paged_q8"]


@pytest.mark.parametrize("kernel", KERNELS)
def test_pallas_call_is_named_after_its_kernel(kernel):
    """Every kernel's ``pallas_call`` carries its function's name: the
    custom call's name in a compiled program and a device trace."""
    fn, args = _kernel_cases()[kernel]
    jx = jax.make_jaxpr(functools.partial(fn, interpret=True))(*args)
    assert [e.params["name"] for e in jx.jaxpr.eqns
            if e.primitive.name == "pallas_call"] == [kernel]


def test_every_pallas_call_has_a_name():
    """No ``pallas_call`` in the kernels package is left unnamed (so a
    new kernel joins ``KERNELS``)."""
    import pathlib
    import repro.kernels
    root = pathlib.Path(repro.kernels.__file__).parent
    calls = names = 0
    for path in root.glob("*.py"):
        src = path.read_text()
        calls += src.count("pl.pallas_call(")
        names += len(re.findall(r'^\s+name="\w+",$', src, re.M))
    assert calls == names == len(KERNELS)
