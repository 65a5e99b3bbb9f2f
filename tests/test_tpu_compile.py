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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import quant
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode_paged
from repro.kernels.flash_prefill import flash_prefill_paged


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
# the level-2 feed-forward down projection (5120 -> 1280).
@pytest.mark.parametrize("k,n", [(320, 320), (768, 320), (1280, 1280),
                                 (5120, 1280)])
@pytest.mark.parametrize("m", [77, 4096])
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
