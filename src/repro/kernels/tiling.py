"""Tile rules shared by the fused-dequant matmul kernels.

Two constraints shape every quantized matmul on the TPU:

* **No partial K block.**  A K tile that overhangs the array reads
  whatever lies past the end (NaN in interpret mode, stale memory on
  the chip) straight into the accumulator.  ``k_block`` therefore only
  returns tiles that divide K exactly.
* **Dequantize in the transposed domain.**  Splitting the lane axis of
  a weight tile into (blocks, 32) is a shape cast Mosaic refuses, and a
  ``(bn, bk/32)`` scale tile breaks the 8x128 block rule for most K.
  The kernels instead widen the packed ``(bn, bk/p)`` tile, transpose
  it to ``(bk/p, bn)`` and unpack along sublanes (``interleave_rows``),
  where every block scale arrives lane-dense as one row of a
  ``(K/blk, N)`` matrix and covers its weights by a sublane broadcast
  (``repeat_rows``).  The MXU then takes a plain ``x @ w`` product.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def k_block(k: int, pref: int, align: int) -> int:
    """K tile: the largest multiple of ``align`` that is at most
    ``pref`` and divides ``k``; ``k`` itself (one block) when none does.

    ``align`` is the smallest K tile whose operand blocks all satisfy
    the 8x128 rule (e.g. 256 for a ``(bk/32, bn)`` f32 scale tile).
    """
    for bk in range(pref - pref % align, 0, -align):
        if k % bk == 0:
            return bk
    return k


def repeat_rows(s: jax.Array, r: int) -> jax.Array:
    """(rows, n) -> (rows * r, n): each row repeated ``r`` times."""
    rows, n = s.shape
    return jnp.broadcast_to(s[:, None, :], (rows, r, n)).reshape(rows * r, n)


def interleave_rows(planes: list[jax.Array]) -> jax.Array:
    """[(rows, n)] * p -> (rows * p, n) with row ``i*p + j`` taken from
    ``planes[j][i]`` — the sublane image of unpacking ``p`` values
    packed per byte, first value in the lowest bits."""
    rows, n = planes[0].shape
    return jnp.stack(planes, axis=1).reshape(rows * len(planes), n)
