"""Pallas TPU kernel: fused-unpack Q3_K matmul.

TPU adaptation of the paper's Q3_K pipeline (Fig. 4).  IMAX3 adds
OP_CVT53 to repack the 6-bit scales / 2+1-bit quants into a unified
SIMD-friendly format inside the PE array; here the same restructuring
happens in VMEM with vectorized shifts/masks on the VPU:

* ``ql`` (2-bit low parts, 4/byte) and ``qh`` (high bits, 8/byte) are
  unpacked and combined to signed 3-bit values in [-4, 3];
* sub-block scales arrive as int8 codes (unpacked from the 12-byte
  6-bit packing by the wrapper — a K/16-sized side input, ~2% of the
  weight bytes) and are expanded to effective multipliers d*(sc-32);
* dequantized bf16 weights feed the MXU; accumulation is f32.

Only ~3.4 bits/weight cross the HBM boundary, which is the paper's core
insight applied to the TPU memory hierarchy.  As in the other
quantized kernels the K tile divides K and the unpack runs in the
transposed domain (``repro.kernels.tiling``): packed bytes are widened
and transposed to (rows, bn), so the 2-bit and 1-bit planes interleave
along sublanes, and the wrapper hands the scales over lane-dense as
(K/16, N) codes and (K/256, N) super-scales.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import N_SUB, Q3K_SUB, QK_K
from repro.kernels.tiling import interleave_rows, k_block, repeat_rows

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 1024
# Smallest K tile whose (bn, bk/8) high-bit block spans 128 lanes.
K_ALIGN = 8 * 128


def _unpack_q3_block(ql, qh):
    """(bn,bk/4) uint8 + (bn,bk/8) uint8 -> (bk,bn) int32 in [-4,3]."""
    ql = ql.astype(jnp.int32).T                               # (bk/4, bn)
    qh = qh.astype(jnp.int32).T                               # (bk/8, bn)
    low = interleave_rows([(ql >> (2 * j)) & 3 for j in range(4)])
    hi = interleave_rows([(qh >> j) & 1 for j in range(8)])
    return (low | (hi << 2)) - 4


def _q3k_kernel(x_ref, ql_ref, qh_ref, sc_ref, d_ref, o_ref, acc_ref,
                *, nk: int):
    """x:(bm,bk) bf16 | ql:(bn,bk/4) | qh:(bn,bk/8) | sc:(bk/16,bn) uint8
    | d:(K/256,bn) f32 (whole column, sliced per K step) -> o:(bm,bn) f32."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    nsup = x_ref.shape[1] // QK_K
    q = _unpack_q3_block(ql_ref[...], qh_ref[...])            # OP_CVT53
    # Effective scale per 16-weight sub-block: d * (sc - 32).
    d = d_ref[pl.ds(k * nsup, nsup), :]                       # (bk/256, bn)
    sc = sc_ref[...].astype(jnp.int32).astype(jnp.float32)    # (bk/16, bn)
    eff = repeat_rows(d, N_SUB) * (sc - 32.0)
    w = q.astype(jnp.float32) * repeat_rows(eff, Q3K_SUB)
    acc_ref[...] += jnp.dot(x_ref[...], w.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def q3k_matmul(x: jax.Array, ql: jax.Array, qh: jax.Array,
               sc: jax.Array, d: jax.Array,
               *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
               bk: int = DEFAULT_BK, interpret: bool = False) -> jax.Array:
    """y = x @ dequant(w).T with w in Q3_K.

    x: (M, K) bf16; ql: (N, K/4) uint8; qh: (N, K/8) uint8;
    sc: (N, K/16) uint8 6-bit codes; d: (N, K/256) super-scales.
    Returns (M, N) f32.
    """
    m, k = x.shape
    n = ql.shape[0]
    assert k % QK_K == 0 and d.shape == (n, k // QK_K)
    bm, bn, bk = min(bm, m), min(bn, n), k_block(k, bk, K_ALIGN)
    nk = k // bk
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), nk)
    return pl.pallas_call(
        functools.partial(_q3k_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk // 4), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bn, bk // 8), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bk // Q3K_SUB, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((k // QK_K, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="q3k_matmul",
        interpret=interpret,
    )(x.astype(jnp.bfloat16), ql, qh, sc.T, d.astype(jnp.float32).T)
