"""Main-path Pallas kernels compile for a TPU v5e at SD-Turbo widths.

Interpret-mode oracles (``test_kernels.py``) show a kernel computes the
right thing, not that Mosaic accepts it: every quantized matmul passed
them while the TPU compiler refused it.  These tests compile each
kernel, with no chip attached, for a described ``v5e:2x2`` topology
and check the compiled program carries the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import quant
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.flash_prefill import (flash_prefill_paged,
                                         flash_prefill_paged_q8)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Compiles for a described chip cannot be read back from the
    # persistent cache, so keep it out of the way while they run.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _weight(sharding, fmt, n, k):
    w = jax.eval_shape(lambda: quant.quantize(jnp.zeros((n, k)), fmt))
    return jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), w)


def _matmul(x, w):
    return ops.quantized_matmul(x, w, force="pallas")


# (K, N) of SD-Turbo's Q8_0 sites: level-0 attention (320), cross-
# attention K/V from the 768-wide context, level-2 attention (1280) and
# the level-2 feed-forward down projection (5120 -> 1280), at M = 77 and
# 4096.  Then SDXL's sites at batch 4, whose ``q8_plan`` tiles are the
# largest: the 1280-wide level's feed-forward up projection, the
# 640-wide level's up and down projections at M = 16384, and cross-
# attention K/V from the 2048-wide context; and SD v1.5's level-0
# feed-forward up projection.  A plan that overflows VMEM fails here.
@pytest.mark.parametrize("m,k,n", [
    (m, k, n) for m in (77, 4096)
    for k, n in ((320, 320), (768, 320), (1280, 1280), (5120, 1280))] + [
    (4096, 1280, 10240), (16384, 640, 5120), (16384, 2560, 640),
    (308, 2048, 1280), (16384, 320, 2560)])
def test_q8_matmul_compiles(one_chip, m, k, n):
    text = _compiled_text(_matmul, _spec(one_chip, (m, k), jnp.bfloat16),
                          _weight(one_chip, "q8_0", n, k))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,k,n", [(77, 768, 768), (4096, 1280, 320)])
def test_q3k_matmul_compiles(one_chip, m, k, n):
    text = _compiled_text(_matmul, _spec(one_chip, (m, k), jnp.bfloat16),
                          _weight(one_chip, "q3_k", n, k))
    assert "tpu_custom_call" in text


# UNet level-0 self-attention (64x64 latent, 8 heads of 40), its cross-
# attention to the 77 CLIP tokens, and CLIP's causal self-attention.
@pytest.mark.parametrize("b,h,sq,sk,d,causal", [
    (2, 8, 4096, 4096, 40, False), (2, 8, 4096, 77, 40, False),
    (2, 12, 77, 77, 64, True)])
def test_flash_attention_compiles(one_chip, b, h, sq, sk, d, causal):
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=causal),
        _spec(one_chip, (b, h, sq, d), jnp.bfloat16),
        _spec(one_chip, (b, h, sk, d), jnp.bfloat16),
        _spec(one_chip, (b, h, sk, d), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_flash_prefill_paged_compiles(one_chip):
    """One 128-token chunk of an 8-KV-head, 4-query-group, 128-dim LM
    against a 16-token-block paged pool."""
    t, h, g, d, bs, nb, mb = 128, 8, 4, 128, 16, 64, 32
    text = _compiled_text(
        flash_prefill_paged,
        _spec(one_chip, (t, h, g, d), jnp.bfloat16),
        _spec(one_chip, (t, h, d), jnp.bfloat16),
        _spec(one_chip, (t, h, d), jnp.bfloat16),
        _spec(one_chip, (nb, h, bs, d), jnp.bfloat16),
        _spec(one_chip, (nb, h, bs, d), jnp.bfloat16),
        _spec(one_chip, (mb,), jnp.int32), _spec(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_decode_paged_compiles(one_chip):
    """One decode step of 8 slots over the same paged pool."""
    b, h, g, d, bs, nb, mb = 8, 8, 4, 128, 16, 64, 32
    text = _compiled_text(
        flash_decode_paged,
        _spec(one_chip, (b, h, g, d), jnp.bfloat16),
        _spec(one_chip, (nb, h, bs, d), jnp.bfloat16),
        _spec(one_chip, (nb, h, bs, d), jnp.bfloat16),
        _spec(one_chip, (b, mb), jnp.int32), _spec(one_chip, (b,), jnp.int32))
    assert "tpu_custom_call" in text


def _named_kernels(sh):
    """(fn, args) per Pallas kernel that compiles for v5e: small shapes
    of each kernel's main path."""
    bf16 = jnp.bfloat16

    def matmul(fmt, m, k, n):
        return _matmul, (_spec(sh, (m, k), bf16), _weight(sh, fmt, n, k))

    t, h, g, d, bs, nb, mb = 128, 8, 4, 128, 16, 64, 32
    return {
        "q8_matmul": matmul("q8_0", 256, 320, 320),
        "q3k_matmul": matmul("q3_k", 256, 768, 768),
        "q4_matmul": matmul("q4_0", 256, 320, 320),
        "flash_attention": (
            lambda q, k, v: flash_attention(q, k, v, causal=False),
            tuple(_spec(sh, (2, 8, 256, 64), bf16) for _ in range(3))),
        "flash_decode": (flash_decode, (
            _spec(sh, (8, h, g, d), bf16), _spec(sh, (8, h, 512, d), bf16),
            _spec(sh, (8, h, 512, d), bf16), _spec(sh, (8,), jnp.int32))),
        "flash_decode_paged": (flash_decode_paged, (
            _spec(sh, (8, h, g, d), bf16), _spec(sh, (nb, h, bs, d), bf16),
            _spec(sh, (nb, h, bs, d), bf16), _spec(sh, (8, mb), jnp.int32),
            _spec(sh, (8,), jnp.int32))),
        "flash_prefill_paged": (flash_prefill_paged, (
            _spec(sh, (t, h, g, d), bf16), _spec(sh, (t, h, d), bf16),
            _spec(sh, (t, h, d), bf16), _spec(sh, (nb, h, bs, d), bf16),
            _spec(sh, (nb, h, bs, d), bf16), _spec(sh, (mb,), jnp.int32),
            _spec(sh, (), jnp.int32))),
    }


@pytest.mark.parametrize("kernel", [
    "q8_matmul", "q3k_matmul", "q4_matmul", "flash_attention",
    "flash_decode", "flash_decode_paged", "flash_prefill_paged"])
def test_compiled_kernel_carries_its_name(one_chip, kernel):
    """A kernel's custom call is named after its function in the
    compiled program: the op name a device trace shows for it."""
    fn, args = _named_kernels(one_chip)[kernel]
    text = _compiled_text(fn, *args)
    assert re.search(rf"^\s*(ROOT )?%{kernel}(\.\d+)? = .* custom-call\(.*"
                     r"custom_call_target=\"tpu_custom_call\"", text, re.M)


def test_flash_prefill_paged_q8_lowers_with_its_name(one_chip):
    """The Q8_0 prefill kernel does not compile for v5e yet (a reshape
    Mosaic refuses); its lowered custom call already names it."""
    t, h, g, d, bs, nb, mb = 128, 8, 4, 128, 16, 64, 32
    text = jax.jit(flash_prefill_paged_q8).lower(
        _spec(one_chip, (t, h, g, d), jnp.bfloat16),
        _spec(one_chip, (t, h, d), jnp.bfloat16),
        _spec(one_chip, (t, h, d), jnp.bfloat16),
        _spec(one_chip, (nb, h, bs, d), jnp.int8),
        _spec(one_chip, (nb, h, bs, d), jnp.int8),
        _spec(one_chip, (nb, h, bs, d // 32), jnp.float16),
        _spec(one_chip, (nb, h, bs, d // 32), jnp.float16),
        _spec(one_chip, (mb,), jnp.int32),
        _spec(one_chip, (), jnp.int32)).as_text()
    assert 'kernel_name = "flash_prefill_paged_q8"' in text
