"""Pallas TPU kernel: fused-dequant Q8_0 matmul (+ integer w8a8 variant).

TPU adaptation of the paper's IMAX3 Q8_0 dot-product pipeline (Fig. 3):

* IMAX streams 32-element quantized blocks through PE-local LMM; the
  int8 multiply-adds (OP_SML8) accumulate into 24-bit (OP_AD24) and a
  final fp32 scale multiply produces the output.
* Here the quantized blocks are staged HBM->VMEM by ``BlockSpec`` tiles;
  only *quantized bytes* cross the bandwidth-limited HBM boundary.  The
  ``dequant`` variant expands int8->bf16 in VMEM (VPU) and feeds the MXU
  — optimal when the layer is memory-bound (decode).  The ``int8``
  variant keeps the integer dot (MXU int8 path, int32 accumulate — a
  superset of OP_AD24's 24 bits) and applies the per-block scale product
  afterwards, faithful to the paper's dataflow.

Grid is (M/bm, N/bn, K/bk) with the K dimension innermost ("arbitrary")
accumulating into a VMEM scratch tile; M/N are parallel.  The K tile
divides K exactly and the weight tile is dequantized in the transposed
domain, with the block scales handed to the kernel lane-dense as
(K/32, N) — see ``repro.kernels.tiling`` for why Mosaic needs both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import QK8_0
from repro.kernels.tiling import k_block, repeat_rows

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512
# Smallest K tile whose (bk/32, bn) f32 scale block keeps 8 sublanes.
K_ALIGN = 8 * QK8_0


def _dequant_kernel(x_ref, wq_ref, ws_ref, o_ref, acc_ref, *, nk: int):
    """x:(bm,bk) bf16 | wq:(bn,bk) int8 | ws:(bk/32,bn) f32 -> o:(bm,bn) f32."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # In-VMEM dequantization: int8 -> f32, transposed to (bk, bn), times
    # the sublane-broadcast scales -> bf16 (never touches HBM).
    w = wq_ref[...].astype(jnp.float32).T * repeat_rows(ws_ref[...], QK8_0)
    acc_ref[...] += jnp.dot(x_ref[...], w.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def q8_matmul(x: jax.Array, wq: jax.Array, ws: jax.Array,
              *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
              bk: int = DEFAULT_BK, interpret: bool = False) -> jax.Array:
    """y = x @ dequant(w).T with w in Q8_0 (fused dequant).

    x: (M, K) bf16; wq: (N, K) int8; ws: (N, K/32) block scales.
    Returns (M, N) f32.
    """
    m, k = x.shape
    n = wq.shape[0]
    assert k % QK8_0 == 0 and ws.shape == (n, k // QK8_0)
    bm, bn, bk = min(bm, m), min(bn, n), k_block(k, bk, K_ALIGN)
    nk = k // bk
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), nk)
    return pl.pallas_call(
        functools.partial(_dequant_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bk // QK8_0, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="q8_matmul",
        interpret=interpret,
    )(x.astype(jnp.bfloat16), wq, ws.astype(jnp.float32).T)


def _w8a8_kernel(xq_ref, xs_ref, wq_ref, ws_ref, o_ref, acc_ref, *, nk: int):
    """Integer path: per-32-block int8 dot + scale product accumulate."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bm, bk = xq_ref.shape
    bn = wq_ref.shape[0]
    nb = bk // QK8_0
    a = xq_ref[...].reshape(bm, nb, QK8_0)
    b = wq_ref[...].reshape(bn, nb, QK8_0)
    # OP_SML8 analogue: int8 x int8 -> int32 block dots (batched over nb).
    ints = jax.lax.dot_general(
        a, b, dimension_numbers=(((2,), (2,)), ((1,), (1,))),
        preferred_element_type=jnp.int32)                    # (nb, bm, bn)
    scaled = (ints.astype(jnp.float32)
              * xs_ref[...].T[:, :, None]
              * ws_ref[...].T[:, None, :])
    acc_ref[...] += jnp.sum(scaled, axis=0)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def q8_matmul_w8a8(xq: jax.Array, xs: jax.Array, wq: jax.Array,
                   ws: jax.Array, *, bm: int = DEFAULT_BM,
                   bn: int = DEFAULT_BN, bk: int = 256,
                   interpret: bool = False) -> jax.Array:
    """Integer-path Q8_0 matmul. xq:(M,K) int8, xs:(M,K/32) f32,
    wq:(N,K) int8, ws:(N,K/32) f32 -> (M,N) f32.

    Interpret-mode only: the per-block (bm, bk/32, 32) split is a shape
    cast Mosaic refuses, so ``ops`` runs the XLA reference on the TPU.
    """
    m, k = xq.shape
    n = wq.shape[0]
    assert k % QK8_0 == 0
    bm, bn, bk = min(bm, m), min(bn, n), k_block(k, bk, QK8_0)
    nk = k // bk
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), nk)
    return pl.pallas_call(
        functools.partial(_w8a8_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, bk // QK8_0), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bn, bk // QK8_0), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="q8_matmul_w8a8",
        interpret=interpret,
    )(xq, xs, wq, ws)
