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

The dequant kernel is weight-stationary: its grid is (N/bn, M/bm, K/bk),
and while the first M tile of an N tile runs, each K step dequantizes
its weight block into a bf16 (K, bn) panel in VMEM that every later M
tile reuses.  Each weight is dequantized and copied from HBM once per
call; later steps are an activation tile's copy and an MXU product.
``q8_plan`` picks the tiles from the call's shape alone.  The K tile
divides K exactly and the weight block is dequantized in the transposed
domain, with the block scales handed to the kernel lane-dense as
(K/32, N) — see ``repro.kernels.tiling`` for why Mosaic needs both.
The w8a8 variant keeps the (M/bm, N/bn, K/bk) grid.
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
# Smallest K tile whose (bk/32, bn) f32 scale block keeps 8 sublanes.
K_ALIGN = 8 * QK8_0

# The dequant kernel's cost model, for ``q8_plan`` (TPU v5e).  A grid
# step costs about STEP_S besides its work: with 128 x 128 output tiles,
# whose MXU work is 0.01-0.04 us a step, the kernel ran at 0.44-0.45 us
# a step on a v5e.
STEP_S = 0.4e-6
PEAK_FLOPS = 197e12
HBM_BYTES_S = 819e9
# VMEM the plan's buffers may take; Mosaic's limit leaves room above it
# for its own scratch.
VMEM_BUDGET = 32 << 20
VMEM_LIMIT = 48 << 20
# Largest M tile: fewer steps, but a longer unpipelined first copy and
# last write-back.
BM_MAX = 1024


def _dequant_rows(bk: int) -> int:
    """K rows of a weight block dequantized at a time: the f32
    temporaries of the whole block could outgrow VMEM."""
    return next((r for r in (512, 256) if bk % r == 0), bk)


def q8_vmem_bytes(bm: int, bn: int, bk: int, k: int) -> int:
    """VMEM of a plan: double-buffered x, weight, scale and output tiles,
    the (K, bn) bf16 panel, the product and the dequantize temporaries."""
    return (2 * bm * bk * 2 + 2 * bn * bk + 2 * (bk // QK8_0) * bn * 4
            + 2 * bm * bn * 4 + k * bn * 2 + bm * bn * 4
            + 3 * _dequant_rows(bk) * bn * 4)


def _q8_seconds(m: int, n: int, k: int, bm: int, bn: int, bk: int) -> float:
    """Modelled time of a plan: per-step cost, then the larger of the
    MXU time (over the padded tiles) and the HBM time (x once per N
    tile, the packed weights once, the f32 output), then the first
    tiles' copy and the last write-back, which no other step hides."""
    mi, nj, nk = pl.cdiv(m, bm), pl.cdiv(n, bn), k // bk
    mxu = 2 * mi * bm * nj * bn * k / PEAK_FLOPS
    hbm = (2 * m * k * nj + n * k * (1 + 4 / QK8_0) + 4 * m * n) / HBM_BYTES_S
    edge = (2 * bm * bk + bn * bk + 4 * bm * bn) / HBM_BYTES_S
    return mi * nj * nk * STEP_S + max(mxu, hbm) + edge


@functools.lru_cache(maxsize=None)
def q8_plan(m: int, n: int, k: int) -> tuple[int, int, int]:
    """Tiles (bm, bn, bk) of the dequant kernel for x (m, k) @ w (n, k)^T:
    of the legal tiles whose buffers fit ``VMEM_BUDGET``, those of the
    least modelled time.

    * bm: all of m up to ``BM_MAX``, else m split evenly into tiles of
      at most 128, 256, 512 or ``BM_MAX`` rows (multiples of 16).
    * bn: all of n, a multiple of 128 that divides n, or 128, 256 or
      512 with a partial last tile.
    * bk: all of k, or a multiple of ``K_ALIGN`` that divides it.
    """
    def even(cap):  # rows per tile of m split evenly into <= cap rows
        return min(m, pl.cdiv(pl.cdiv(m, pl.cdiv(m, cap)), 16) * 16)

    bms = {m} if m <= BM_MAX else set()
    bms |= {even(cap) for cap in (128, 256, 512, BM_MAX) if cap < m}
    bns = {n} | {b for b in range(128, n, 128)
                 if n % b == 0 or b in (128, 256, 512)}
    bks = {k} | {b for b in range(K_ALIGN, k, K_ALIGN) if k % b == 0}
    plans = [(bm, bn, bk) for bm in bms for bn in bns for bk in bks
             if q8_vmem_bytes(bm, bn, bk, k) <= VMEM_BUDGET]
    if not plans:
        raise ValueError(f"no q8_matmul tiles fit VMEM for {(m, n, k)}")
    return min(plans, key=lambda p: (_q8_seconds(m, n, k, *p),
                                     [-t for t in p]))


def _dequant_kernel(x_ref, wq_ref, ws_ref, o_ref, panel_ref, *, nk: int):
    """x:(bm,bk) bf16 | wq:(bn,bk) int8 | ws:(bk/32,bn) f32 -> o:(bm,bn)
    f32, summed over the K steps in place; panel:(K,bn) bf16 scratch."""
    i, kk = pl.program_id(1), pl.program_id(2)
    bk = x_ref.shape[1]
    row = 0 if nk == 1 else pl.multiple_of(kk * bk, K_ALIGN)

    # First M tile: in-VMEM dequantization into the panel, block by
    # block: int8 -> f32, transposed to (rows, bn), times the sublane-
    # broadcast scales -> bf16 (never touches HBM).
    @pl.when(i == 0)
    def _dequant():
        r = _dequant_rows(bk)
        for c in range(0, bk, r):
            w = (wq_ref[:, c:c + r].astype(jnp.float32).T
                 * repeat_rows(ws_ref[c // QK8_0:(c + r) // QK8_0, :],
                               QK8_0))
            panel_ref[pl.ds(row + c, r), :] = w.astype(jnp.bfloat16)

    part = jnp.dot(x_ref[...], panel_ref[pl.ds(row, bk), :],
                   preferred_element_type=jnp.float32)

    @pl.when(kk == 0)
    def _first():
        o_ref[...] = part

    @pl.when(kk > 0)
    def _rest():
        o_ref[...] += part


def q8_matmul(x: jax.Array, wq: jax.Array, ws: jax.Array,
              *, interpret: bool = False) -> jax.Array:
    """y = x @ dequant(w).T with w in Q8_0 (fused dequant).

    x: (M, K) bf16; wq: (N, K) int8; ws: (N, K/32) block scales.
    Returns (M, N) f32.
    """
    m, k = x.shape
    n = wq.shape[0]
    assert k % QK8_0 == 0 and ws.shape == (n, k // QK8_0)
    bm, bn, bk = q8_plan(m, n, k)
    nk = k // bk
    # After the first M tile the weight and scale blocks keep the last
    # K block's index, so the pipeline copies them no more.
    kw = lambda i, kk: jnp.minimum(kk + i * nk, nk - 1)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_dequant_kernel, nk=nk),
        grid=(pl.cdiv(n, bn), pl.cdiv(m, bm), nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, i, kk: (i, kk)),
            pl.BlockSpec((bn, bk), lambda j, i, kk: (j, kw(i, kk))),
            pl.BlockSpec((bk // QK8_0, bn),
                         lambda j, i, kk: (kw(i, kk), j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, bn), jnp.bfloat16)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
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
