"""Pallas TPU kernel: fused-unpack Q4_0 matmul.

Same structure as the Q8_0 kernel, with an in-VMEM nibble unpack
(two 4-bit quants per byte, offset 8): only 4.5 bits/weight cross the
HBM boundary.  Grid (M/bm, N/bn, K/bk), K innermost accumulating into
a VMEM scratch tile; the K tile divides K and the unpack runs in the
transposed domain (``repro.kernels.tiling``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import QK8_0
from repro.kernels.tiling import interleave_rows, k_block, repeat_rows

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512
# Smallest K tile whose (bk/32, bn) f32 scale block keeps 8 sublanes
# (the (bn, bk/2) byte tile then spans 128 lanes).
K_ALIGN = 8 * QK8_0


def _q4_kernel(x_ref, qs_ref, ws_ref, o_ref, acc_ref, *, nk: int):
    """x:(bm,bk) bf16 | qs:(bn,bk/2) uint8 | ws:(bk/32,bn) f32."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qs = qs_ref[...].astype(jnp.int32).T                 # (bk/2, bn)
    q = interleave_rows([(qs & 0x0F) - 8, ((qs >> 4) & 0x0F) - 8])
    w = q.astype(jnp.float32) * repeat_rows(ws_ref[...], QK8_0)
    acc_ref[...] += jnp.dot(x_ref[...], w.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def q4_matmul(x: jax.Array, qs: jax.Array, ws: jax.Array,
              *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
              bk: int = DEFAULT_BK, interpret: bool = False) -> jax.Array:
    """y = x @ dequant(w).T with w in Q4_0.

    x: (M, K) bf16; qs: (N, K/2) uint8; ws: (N, K/32) block scales.
    Returns (M, N) f32.
    """
    m, k = x.shape
    n = qs.shape[0]
    assert k % QK8_0 == 0 and ws.shape == (n, k // QK8_0)
    bm, bn, bk = min(bm, m), min(bn, n), k_block(k, bk, K_ALIGN)
    nk = k // bk
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), nk)
    return pl.pallas_call(
        functools.partial(_q4_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk // 2), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bk // QK8_0, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="q4_matmul",
        interpret=interpret,
    )(x.astype(jnp.bfloat16), qs, ws.astype(jnp.float32).T)
