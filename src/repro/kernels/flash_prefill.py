"""Pallas TPU kernel: fused paged flash-prefill (multi-token chunk).

Admission used to scan the decode step token-by-token; this kernel
attends an entire prompt chunk ``(T, Hkv, G, hd)`` in ONE program
against the paged KV pool — the fused multi-token prefill the CGLA-LLM
companion study singles out as the phase where kernel fusion pays off.

Per grid step ``(h, j)`` the kernel

1. **writes** the chunk's keys/values that land in physical block
   ``table[j]`` (an in-kernel scatter expressed as a one-hot matmul, so
   it lowers to the MXU instead of a per-row dynamic store), then
2. **attends** all T queries to that block with online softmax:
   causal masking *within* the chunk (query ``t`` sees chunk tokens
   ``<= t``) and per-row position masking against prior blocks
   (positions ``< pos0`` are history, positions ``>= pos0 + T`` are a
   recycled block's stale bytes and are value-neutralized like the
   decode kernel).

The pool outputs are aliased onto the pool inputs
(``input_output_aliases``), so blocks not named by the table are
untouched and the chunk's KV lands in place — one kernel launch per
chunk replaces T decode-step launches.

Layouts: q ``(T, Hkv, G, hd)``; k_new/v_new ``(T, Hkv, hd)``;
pools ``(NB, Hkv, bs, hd)``; block_table ``(MB,)`` int32;
pos0 scalar int32 (tokens already cached for this slot).

``flash_prefill_paged_q8`` is the Q8_0 sibling for quantized KV pools:
same grid and write discipline, but the chunk's KV is **requantized
in-kernel** (per-32 blocks along ``hd``, GGML Q8_0 semantics identical
to ``core.quant.quantize_q8_0``) and scattered into int8 quant pools
plus fp16 scale pools — four aliased pool outputs instead of two.  The
block is dequantized to bf16 after the merge — the precision the scan
path's ``_dequantize_kv`` reads the pool at — so the chunk's own tokens
attend to exactly what later decode steps will read (matching the scan
path's quantize-then-dequantize round trip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant

NEG_INF = -1e30
QK = quant.QK8_0  # 32: Q8_0 block size along head_dim


def _prefill_kernel(tbl_ref, pos_ref, q_ref, kn_ref, vn_ref, kp_ref, vp_ref,
                    o_ref, ko_ref, vo_ref, m_ref, l_ref, acc_ref, *,
                    scale: float, g: int, t: int, bs: int, mb: int,
                    window: int | None):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos0 = pos_ref[0]
    # ---- in-kernel KV write: chunk rows landing in this block ----
    # Global position of block offset c is j*bs + c; the chunk row that
    # lands there is r = j*bs + c - pos0 (if 0 <= r < t).  Expressed as
    # a one-hot (bs, t) matmul so the scatter runs on the MXU.
    kcol = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    row = kcol - pos0                                       # (bs, 1)
    write = (row >= 0) & (row < t)                          # (bs, 1)
    onehot = (row == jax.lax.broadcasted_iota(
        jnp.int32, (bs, t), 1)).astype(jnp.float32)         # (bs, t)
    k_wr = jax.lax.dot_general(
        onehot, kn_ref[0].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())))         # (bs, hd)
    v_wr = jax.lax.dot_general(
        onehot, vn_ref[0].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())))
    k_blk = jnp.where(write, k_wr.astype(kp_ref.dtype), kp_ref[0, 0])
    v_blk = jnp.where(write, v_wr.astype(vp_ref.dtype), vp_ref[0, 0])
    ko_ref[0, 0] = k_blk
    vo_ref[0, 0] = v_blk

    # ---- attend all T queries to the (now current) block ----
    q = q_ref[0]                                            # (t*g, hd)
    logits = jax.lax.dot_general(
        q, k_blk, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale         # (t*g, bs)
    qpos = pos0 + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 0) // g
    kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    mask = kpos <= qpos                  # history + intra-chunk causal
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, NEG_INF)
    # Positions past the chunk's last token are a recycled block's
    # stale bytes; masked p is ~0 but 0 * NaN = NaN, so zero the values.
    v_use = jnp.where(kcol < pos0 + t, v_blk, 0.0)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
    p = jnp.exp(logits - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v_use.dtype), v_use,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == mb - 1)
    def _done():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)
                    ).astype(o_ref.dtype)


def flash_prefill_paged(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                        k_pool: jax.Array, v_pool: jax.Array,
                        block_table: jax.Array, pos0: jax.Array, *,
                        scale: float | None = None,
                        window: int | None = None,
                        interpret: bool = False
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused prefill of one chunk for one slot.

    q: (T, Hkv, G, hd); k_new/v_new: (T, Hkv, hd);
    k/v pools: (NB, Hkv, bs, hd); block_table: (MB,) int32;
    pos0: scalar int32 — tokens already cached (the chunk occupies
    positions ``pos0 .. pos0+T-1``).

    Returns ``(out (T, Hkv, G, hd), k_pool', v_pool')`` where the
    pools carry the chunk's KV written in place (outputs are aliased
    onto the pool inputs; unlisted blocks are untouched).
    """
    t, h, g, d = q.shape
    bs = k_pool.shape[2]
    mb = block_table.shape[0]
    if scale is None:
        scale = d ** -0.5
    qf = q.transpose(1, 0, 2, 3).reshape(h, t * g, d)
    knf = k_new.transpose(1, 0, 2)
    vnf = v_new.transpose(1, 0, 2)
    pos0 = jnp.asarray(pos0, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(h, mb),
        in_specs=[
            pl.BlockSpec((1, t * g, d),
                         lambda hi, j, tbl, pos: (hi, 0, 0)),
            pl.BlockSpec((1, t, d),
                         lambda hi, j, tbl, pos: (hi, 0, 0)),
            pl.BlockSpec((1, t, d),
                         lambda hi, j, tbl, pos: (hi, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda hi, j, tbl, pos: (tbl[j], hi, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda hi, j, tbl, pos: (tbl[j], hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, t * g, d),
                         lambda hi, j, tbl, pos: (hi, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda hi, j, tbl, pos: (tbl[j], hi, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda hi, j, tbl, pos: (tbl[j], hi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((t * g, 1), jnp.float32),
            pltpu.VMEM((t * g, 1), jnp.float32),
            pltpu.VMEM((t * g, d), jnp.float32),
        ],
    )
    out, kp, vp = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, g=g, t=t, bs=bs,
                          mb=mb, window=window),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((h, t * g, d), q.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # Inputs are numbered incl. the two scalar-prefetch operands:
        # 5/6 are k_pool/v_pool -> outputs 1/2 (in-place KV writes).
        input_output_aliases={5: 1, 6: 2},
        name="flash_prefill_paged",
        interpret=interpret,
    )(block_table.astype(jnp.int32), pos0, qf, knf, vnf, k_pool, v_pool)
    return out.reshape(h, t, g, d).transpose(1, 0, 2, 3), kp, vp


def flash_prefill_paged_ref(q, k_new, v_new, k_pool, v_pool, block_table,
                            pos0, *, scale=None, window=None):
    """Oracle (plain XLA): scatter the chunk into the pools, gather the
    table, causal + position-masked softmax.  Also the CPU serving path
    (`ops.paged_prefill_attention` dispatches here off-TPU)."""
    t, h, g, d = q.shape
    bs = k_pool.shape[2]
    mb = block_table.shape[0]
    if scale is None:
        scale = d ** -0.5
    pos0 = jnp.asarray(pos0, jnp.int32).reshape(())
    chunk_pos = pos0 + jnp.arange(t)
    bids = block_table[chunk_pos // bs]
    offs = chunk_pos % bs
    k_pool = k_pool.at[bids, :, offs].set(k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[bids, :, offs].set(v_new.astype(v_pool.dtype))

    def gather(pool):
        gth = pool[block_table]                # (MB, Hkv, bs, hd)
        return gth.transpose(1, 0, 2, 3).reshape(h, mb * bs, d)

    keys, vals = gather(k_pool), gather(v_pool)
    logits = jnp.einsum("thgd,hcd->thgc", q.astype(jnp.float32),
                        keys.astype(jnp.float32)) * scale
    qpos = chunk_pos[:, None]
    kpos = jnp.arange(mb * bs)[None, :]
    mask = kpos <= qpos                                     # (t, C)
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    # Stale bytes past the chunk's last token: 0 * NaN guard.
    vals = jnp.where((kpos[0] < pos0 + t)[None, :, None], vals, 0)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    out = jnp.einsum("thgc,hcd->thgd", p, vals.astype(jnp.float32))
    return out.astype(q.dtype), k_pool, v_pool


# ------------------------------------------------------------- Q8_0 KV


def _q8_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Row-wise Q8_0 over per-32 blocks of the last axis.

    Delegates to ``quant.quantize_q8_0`` so the in-kernel requantization
    is definitionally the same math as the scan path's ``_quantize_kv``
    (fp16 scale saturation included).  Returns ``(q f32, d f32-via-f16)``
    — quants stay f32 so the scatter runs on the MXU; the f32<->int8 and
    f32<->f16 round trips are exact for these values.
    """
    t8 = quant.quantize_q8_0(x.astype(jnp.float32))
    return t8.qs.astype(jnp.float32), t8.d.astype(jnp.float32)


def _prefill_kernel_q8(tbl_ref, pos_ref, q_ref, kn_ref, vn_ref,
                       kqp_ref, vqp_ref, ksp_ref, vsp_ref,
                       o_ref, kqo_ref, vqo_ref, kso_ref, vso_ref,
                       m_ref, l_ref, acc_ref, *,
                       scale: float, g: int, t: int, bs: int, mb: int,
                       window: int | None):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos0 = pos_ref[0]
    ds = q_ref.shape[-1] // QK                              # scale cols
    # ---- requantize the chunk's KV rows (Q8_0 per 32 along hd) ----
    k_q, k_d = _q8_rows(kn_ref[0])                          # (t,d) (t,ds)
    v_q, v_d = _q8_rows(vn_ref[0])
    # ---- in-kernel scatter of quants AND scales into this block ----
    kcol = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    row = kcol - pos0                                       # (bs, 1)
    write = (row >= 0) & (row < t)                          # (bs, 1)
    onehot = (row == jax.lax.broadcasted_iota(
        jnp.int32, (bs, t), 1)).astype(jnp.float32)         # (bs, t)

    def scatter(chunk_rows, pool_ref):
        wr = jax.lax.dot_general(
            onehot, chunk_rows,
            dimension_numbers=(((1,), (0,)), ((), ())))
        return jnp.where(write, wr, pool_ref[0, 0].astype(jnp.float32))

    kq_blk = scatter(k_q, kqp_ref)                          # (bs, d) f32
    vq_blk = scatter(v_q, vqp_ref)
    ks_blk = scatter(k_d, ksp_ref)                          # (bs, ds) f32
    vs_blk = scatter(v_d, vsp_ref)
    kqo_ref[0, 0] = kq_blk.astype(kqo_ref.dtype)            # int8, exact
    vqo_ref[0, 0] = vq_blk.astype(vqo_ref.dtype)
    kso_ref[0, 0] = ks_blk.astype(kso_ref.dtype)            # f16, exact
    vso_ref[0, 0] = vs_blk.astype(vso_ref.dtype)

    # ---- dequantize the merged block and attend ----
    # Dequant rounds through bf16 — the precision the scan path's
    # _dequantize_kv reads the pool at — then computes in f32 exactly
    # like the decode oracle, so fused and scan attention see
    # bit-identical K/V and diverge only by accumulation order.
    d = q_ref.shape[-1]
    k_deq = (kq_blk.reshape(bs, ds, QK) * ks_blk[..., None]
             ).reshape(bs, d).astype(jnp.bfloat16).astype(jnp.float32)
    v_deq = (vq_blk.reshape(bs, ds, QK) * vs_blk[..., None]
             ).reshape(bs, d).astype(jnp.bfloat16).astype(jnp.float32)
    q = q_ref[0].astype(jnp.float32)                        # (t*g, hd)
    logits = jax.lax.dot_general(
        q, k_deq, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale         # (t*g, bs)
    qpos = pos0 + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 0) // g
    kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    mask = kpos <= qpos                  # history + intra-chunk causal
    if window is not None:
        mask &= kpos > qpos - window
    # Stale scales in a recycled block may be NaN: every stale column is
    # masked (kpos >= pos0 + t > qpos), so `where` replaces its NaN
    # logits with NEG_INF before the row max.
    logits = jnp.where(mask, logits, NEG_INF)
    v_use = jnp.where(kcol < pos0 + t, v_deq, 0.0)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
    p = jnp.exp(logits - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v_use, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == mb - 1)
    def _done():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)
                    ).astype(o_ref.dtype)


def flash_prefill_paged_q8(q: jax.Array, k_new: jax.Array,
                           v_new: jax.Array,
                           kq_pool: jax.Array, vq_pool: jax.Array,
                           ks_pool: jax.Array, vs_pool: jax.Array,
                           block_table: jax.Array, pos0: jax.Array, *,
                           scale: float | None = None,
                           window: int | None = None,
                           interpret: bool = False):
    """Fused Q8_0 prefill of one chunk for one slot.

    q: (T, Hkv, G, hd); k_new/v_new: (T, Hkv, hd) **unquantized**;
    kq/vq pools: (NB, Hkv, bs, hd) int8; ks/vs pools:
    (NB, Hkv, bs, hd // 32) fp16; block_table: (MB,) int32; pos0:
    scalar int32.

    Returns ``(out, kq_pool', vq_pool', ks_pool', vs_pool')`` with the
    chunk's KV requantized in-kernel and written in place (all four
    pool outputs aliased; unlisted blocks untouched).
    """
    t, h, g, d = q.shape
    if d % QK:
        raise ValueError(f"head_dim {d} not divisible by {QK}")
    bs = kq_pool.shape[2]
    ds = d // QK
    mb = block_table.shape[0]
    if scale is None:
        scale = d ** -0.5
    qf = q.transpose(1, 0, 2, 3).reshape(h, t * g, d)
    knf = k_new.transpose(1, 0, 2)
    vnf = v_new.transpose(1, 0, 2)
    pos0 = jnp.asarray(pos0, jnp.int32).reshape(1)
    quant_spec = pl.BlockSpec((1, 1, bs, d),
                              lambda hi, j, tbl, pos: (tbl[j], hi, 0, 0))
    scale_spec = pl.BlockSpec((1, 1, bs, ds),
                              lambda hi, j, tbl, pos: (tbl[j], hi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(h, mb),
        in_specs=[
            pl.BlockSpec((1, t * g, d),
                         lambda hi, j, tbl, pos: (hi, 0, 0)),
            pl.BlockSpec((1, t, d),
                         lambda hi, j, tbl, pos: (hi, 0, 0)),
            pl.BlockSpec((1, t, d),
                         lambda hi, j, tbl, pos: (hi, 0, 0)),
            quant_spec, quant_spec, scale_spec, scale_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, t * g, d),
                         lambda hi, j, tbl, pos: (hi, 0, 0)),
            quant_spec, quant_spec, scale_spec, scale_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((t * g, 1), jnp.float32),
            pltpu.VMEM((t * g, 1), jnp.float32),
            pltpu.VMEM((t * g, d), jnp.float32),
        ],
    )
    out, kq, vq, ks, vs = pl.pallas_call(
        functools.partial(_prefill_kernel_q8, scale=scale, g=g, t=t,
                          bs=bs, mb=mb, window=window),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((h, t * g, d), q.dtype),
            jax.ShapeDtypeStruct(kq_pool.shape, kq_pool.dtype),
            jax.ShapeDtypeStruct(vq_pool.shape, vq_pool.dtype),
            jax.ShapeDtypeStruct(ks_pool.shape, ks_pool.dtype),
            jax.ShapeDtypeStruct(vs_pool.shape, vs_pool.dtype),
        ],
        # Inputs numbered incl. the two scalar-prefetch operands: 5..8
        # are kq/vq/ks/vs pools -> outputs 1..4 (in-place KV writes).
        input_output_aliases={5: 1, 6: 2, 7: 3, 8: 4},
        name="flash_prefill_paged_q8",
        interpret=interpret,
    )(block_table.astype(jnp.int32), pos0, qf, knf, vnf,
      kq_pool, vq_pool, ks_pool, vs_pool)
    return out.reshape(h, t, g, d).transpose(1, 0, 2, 3), kq, vq, ks, vs


def flash_prefill_paged_q8_ref(q, k_new, v_new, kq_pool, vq_pool,
                               ks_pool, vs_pool, block_table, pos0, *,
                               scale=None, window=None):
    """Oracle (plain XLA) for the Q8_0 fused prefill: requantize the
    chunk with ``quant.quantize_q8_0``, scatter quants + scales, gather
    the table, dequantize to bf16, causal + position-masked softmax.
    Also the CPU serving path for quantized pools."""
    t, h, g, d = q.shape
    bs = kq_pool.shape[2]
    mb = block_table.shape[0]
    ds = d // QK
    if scale is None:
        scale = d ** -0.5
    pos0 = jnp.asarray(pos0, jnp.int32).reshape(())
    chunk_pos = pos0 + jnp.arange(t)
    bids = block_table[chunk_pos // bs]
    offs = chunk_pos % bs
    k8 = quant.quantize_q8_0(k_new.astype(jnp.float32))  # (t, Hkv, d)
    v8 = quant.quantize_q8_0(v_new.astype(jnp.float32))
    kq_pool = kq_pool.at[bids, :, offs].set(k8.qs)
    vq_pool = vq_pool.at[bids, :, offs].set(v8.qs)
    ks_pool = ks_pool.at[bids, :, offs].set(k8.d.astype(ks_pool.dtype))
    vs_pool = vs_pool.at[bids, :, offs].set(v8.d.astype(vs_pool.dtype))

    def gather_deq(qpool, spool):
        gq = qpool[block_table].astype(jnp.float32)  # (MB, Hkv, bs, d)
        gs = spool[block_table].astype(jnp.float32)  # (MB, Hkv, bs, ds)
        deq = (gq.reshape(mb, h, bs, ds, QK) * gs[..., None]
               ).reshape(mb, h, bs, d)
        # Round through bf16 — the precision the scan path's
        # _dequantize_kv reads the pool at — then compute in f32 like
        # the decode oracle.
        return (deq.transpose(1, 0, 2, 3).reshape(h, mb * bs, d)
                .astype(jnp.bfloat16).astype(jnp.float32))

    keys, vals = gather_deq(kq_pool, ks_pool), gather_deq(vq_pool,
                                                          vs_pool)
    logits = jnp.einsum("thgd,hcd->thgc", q.astype(jnp.float32),
                        keys) * scale
    qpos = chunk_pos[:, None]
    kpos = jnp.arange(mb * bs)[None, :]
    mask = kpos <= qpos                                     # (t, C)
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    # Stale bytes (possibly NaN scales) past the chunk's last token.
    vals = jnp.where((kpos[0] < pos0 + t)[None, :, None], vals, 0)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    out = jnp.einsum("thgc,hcd->thgd", p, vals)
    return out.astype(q.dtype), kq_pool, vq_pool, ks_pool, vs_pool
