"""Pallas TPU kernel: flash-decode (one-token GQA attention vs cache).

The decode-path analogue of flash attention: queries are the G query
heads per KV head at a single position; keys/values are the (possibly
ring-buffer) cache.  Validity is a *dynamic* length (`kv_len`, an SMEM
scalar): slots >= kv_len are masked.  Online-softmax over cache chunks
keeps the (G, C) logits in VMEM — on HBM the step reads only the cache
and writes (G, hd).

q: (B, Hkv, G, hd); k/v: (B, Hkv, C, hd); kv_len: (1,) int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BK = 1024
NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale: float, nk: int,
                   bk: int):
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                    # (G, hd)
    k = k_ref[0]                                    # (bk, hd)
    logits = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (G, bk)
    kpos = kk * bk + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(kpos < len_ref[0], logits, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
    p = jnp.exp(logits - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kk == nk - 1)
    def _done():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)
                    ).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 kv_len: jax.Array, *, scale: float | None = None,
                 bk: int = DEFAULT_BK,
                 interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, G, hd); k/v: (B, Hkv, C, hd); kv_len: (1,) int32."""
    b, h, g, d = q.shape
    c = k.shape[2]
    if scale is None:
        scale = d ** -0.5
    bk = min(bk, c)
    nk = pl.cdiv(c, bk)
    qf = q.reshape(b * h, g, d)
    kf = k.reshape(b * h, c, d)
    vf = v.reshape(b * h, c, d)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, nk=nk, bk=bk),
        grid=(b * h, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, g, d), lambda gi, j: (gi, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda gi, j: (gi, j, 0)),
            pl.BlockSpec((1, bk, d), lambda gi, j: (gi, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda gi, j: (gi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        name="flash_decode",
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qf, kf, vf)
    return out.reshape(b, h, g, d)


def _paged_decode_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float, mb: int,
                         bs: int):
    bi = pl.program_id(0)
    ji = pl.program_id(2)

    @pl.when(ji == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                 # (G, hd)
    k = k_ref[0, 0]                                 # (bs, hd)
    logits = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (G, bs)
    # Per-row position mask: logical index ji*bs + c is valid iff it is
    # <= positions[bi] (positions = last written index, inclusive).
    kpos = ji * bs + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(kpos <= pos_ref[bi], logits, NEG_INF)
    # Pool blocks are recycled, not zeroed: the masked tail of a block
    # may hold stale bytes.  Masked probabilities are (near) zero, but
    # 0 * NaN = NaN, so neutralize the values too.
    vpos = ji * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    v = jnp.where(vpos <= pos_ref[bi], v_ref[0, 0], 0.0)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
    p = jnp.exp(logits - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ji == mb - 1)
    def _done():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)
                       ).astype(o_ref.dtype)


def flash_decode_paged(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       block_tables: jax.Array, positions: jax.Array, *,
                       scale: float | None = None,
                       interpret: bool = False) -> jax.Array:
    """Paged flash-decode: gather-by-block-table with per-row masking.

    q: (B, Hkv, G, hd); k/v pools: (NB, Hkv, bs, hd);
    block_tables: (B, MB) int32 physical block ids per slot;
    positions: (B,) int32 — last valid logical index per row
    (inclusive; the serving runtime passes the position it just wrote).

    The block table and positions ride in as scalar-prefetch operands,
    so each grid step's DMA fetches exactly one physical block — the
    HBM traffic of a decode step is the slot's *logical* cache, not the
    whole pool.  Rows must have at least one valid position.
    """
    b, h, g, d = q.shape
    bs = k_pool.shape[2]
    mb = block_tables.shape[1]
    if scale is None:
        scale = d ** -0.5
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, mb),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda bi, hi, ji, tbl, pos: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda bi, hi, ji, tbl, pos:
                         (tbl[bi, ji], hi, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda bi, hi, ji, tbl, pos:
                         (tbl[bi, ji], hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda bi, hi, ji, tbl, pos: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, mb=mb, bs=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, g, d), q.dtype),
        name="flash_decode_paged",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), positions.astype(jnp.int32),
      q, k_pool, v_pool)
    return out


def flash_decode_paged_ref(q, k_pool, v_pool, block_tables, positions, *,
                           scale=None):
    """Oracle: gather blocks, mask idx <= positions[b], softmax."""
    b, h, g, d = q.shape
    bs = k_pool.shape[2]
    mb = block_tables.shape[1]
    if scale is None:
        scale = d ** -0.5

    def gather(pool):
        gth = pool[block_tables]                   # (B, MB, Hkv, bs, hd)
        return gth.transpose(0, 2, 1, 3, 4).reshape(b, h, mb * bs, d)

    keys, vals = gather(k_pool), gather(v_pool)
    logits = jnp.einsum("bhgd,bhcd->bhgc", q.astype(jnp.float32),
                        keys.astype(jnp.float32)) * scale
    valid = jnp.arange(mb * bs)[None, :] <= positions[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
    vals = jnp.where(valid[:, None, :, None], vals, 0)  # 0 * NaN guard
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhgc,bhcd->bhgd", p,
                      vals.astype(jnp.float32)).astype(q.dtype)


def flash_decode_ref(q, k, v, kv_len, *, scale=None):
    """Oracle: masked softmax attention at one position."""
    b, h, g, d = q.shape
    c = k.shape[2]
    if scale is None:
        scale = d ** -0.5
    logits = jnp.einsum("bhgd,bhcd->bhgc", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    valid = jnp.arange(c)[None, None, None, :] < kv_len[0]
    logits = jnp.where(valid, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhgc,bhcd->bhgd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
