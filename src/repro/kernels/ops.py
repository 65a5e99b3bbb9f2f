"""jit'd public entry points for the kernels, with backend dispatch.

On TPU the Pallas kernels run natively.  On CPU (this container, and the
multi-pod dry-run's 512 host devices) we lower the *same math* through
plain-XLA paths (``ref``-equivalent) so that:

* smoke tests and the end-to-end examples run fast on CPU;
* the dry-run HLO carries the true quantized dtypes (int8/uint8 weight
  buffers), so ``cost_analysis`` byte counts reflect the paper's
  bandwidth savings;
* Pallas kernels are still exercised in ``interpret=True`` mode by the
  kernel test-suite.

Set ``force="pallas" | "xla" | "interpret"`` to override dispatch.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.quant import Q3KTensor, Q4_0Tensor, Q8_0Tensor
from repro.kernels import ref
from repro.kernels import q8_matmul as _q8
from repro.kernels import q4_matmul as _q4
from repro.kernels import q3k_matmul as _q3k
from repro.kernels import flash_attention as _fa
from repro.kernels import flash_prefill as _fp

Force = Literal["auto", "pallas", "xla", "interpret"]


def _use_pallas(force: Force) -> tuple[bool, bool]:
    """-> (use_pallas_kernel, interpret)."""
    if force == "pallas":
        return True, False
    if force == "interpret":
        return True, True
    if force == "xla":
        return False, False
    return (jax.default_backend() == "tpu"), False


def _flatten_lead(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _pad_k(x: jax.Array, kp: int) -> jax.Array:
    """Zero-pad activations to a tail-padded (``logical``) tensor's
    stored K: the pad quants are zero, so the product is unchanged."""
    return x if x.shape[-1] == kp else jnp.pad(
        x, ((0, 0), (0, kp - x.shape[-1])))


def quantized_matmul(x: jax.Array, w, *, force: Force = "auto",
                     out_dtype=None) -> jax.Array:
    """y[..., n] = x[..., k] @ dequant(w)[n, k] for Q8_0 / Q3_K weights.

    The weight tensor keeps its quantized storage in HBM; dequantization
    is fused into the matmul (Pallas) or expressed as an int8-load +
    convert + dot in XLA (same byte traffic).
    """
    out_dtype = out_dtype or x.dtype
    xf, lead = _flatten_lead(x)
    use_pallas, interp = _use_pallas(force)
    if isinstance(w, Q8_0Tensor):
        n = w.qs.shape[0]
        if use_pallas:
            y = _q8.q8_matmul(_pad_k(xf, w.qs.shape[-1]), w.qs, w.d,
                              interpret=interp)
        else:
            y = ref.q8_matmul_ref(xf, w)
    elif isinstance(w, Q4_0Tensor):
        n = w.qs.shape[0]
        if use_pallas:
            y = _q4.q4_matmul(_pad_k(xf, 2 * w.qs.shape[-1]), w.qs, w.d,
                              interpret=interp)
        else:
            y = ref.q4_matmul_ref(xf, w)
    elif isinstance(w, Q3KTensor):
        n = w.ql.shape[0]
        if use_pallas:
            sc = quant.unpack_scales6(w.scales).reshape(n, -1)
            y = _q3k.q3k_matmul(xf, w.ql, w.qh, sc, w.d, interpret=interp)
        else:
            y = ref.q3k_matmul_ref(xf, w)
    else:  # plain dense fallback: w is (N, K) array
        n = w.shape[0]
        y = jax.lax.dot_general(
            xf.astype(w.dtype), w,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return y.reshape(*lead, n).astype(out_dtype)


def quantized_matmul_w8a8(x: jax.Array, w: Q8_0Tensor, *,
                          force: Force = "auto",
                          out_dtype=None) -> jax.Array:
    """Integer-path (OP_SML8) matmul: activations quantized to Q8 blocks."""
    out_dtype = out_dtype or x.dtype
    xf, lead = _flatten_lead(x)
    xa = quant.quantize_q8_0(xf)
    xs = xa.d.astype(jnp.float32)
    # The w8a8 kernel runs only through the interpreter: Mosaic refuses
    # its per-block split, so the TPU takes the XLA path.
    _, interp = _use_pallas(force)
    if interp:
        y = _q8.q8_matmul_w8a8(xa.qs, xs, w.qs, w.d.astype(jnp.float32),
                               interpret=interp)
    else:
        y = ref.q8_matmul_w8a8_ref(xa.qs, xs, w)
    return y.reshape(*lead, w.qs.shape[0]).astype(out_dtype)


def _chunked_attention(q, k, v, *, causal, window, scale,
                       q_chunk: int) -> jax.Array:
    """Query-chunked attention for the XLA path: peak intermediate is
    (B, H, q_chunk, Sk) instead of (B, H, Sq, Sk) — the flash-kernel
    memory behaviour expressed in plain XLA (scan over query chunks)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nc = sq // q_chunk
    qs = q.reshape(b, h, nc, q_chunk, d).transpose(2, 0, 1, 3, 4)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def body(_, args):
        ci, qc = args                              # qc: (B,H,bq,D)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qc.astype(jnp.float32),
                            kf) * scale
        qpos = ci * q_chunk + jnp.arange(q_chunk)[:, None] + (sk - sq)
        kpos = jnp.arange(sk)[None, :]
        mask = jnp.ones((q_chunk, sk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p)
        return None, jnp.einsum("bhqk,bhkd->bhqd", p, vf)

    _, out = jax.lax.scan(body, None, (jnp.arange(nc), qs))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, sq, d).astype(q.dtype)


ATTN_CHUNK = 1024


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int | None = None,
              scale: float | None = None,
              force: Force = "auto",
              q_chunk: int | None = None) -> jax.Array:
    """Flash attention with GQA folding. q:(B,Hq,Sq,D), k/v:(B,Hkv,Sk,D).

    ``q_chunk=0`` forces the unchunked XLA path (cost probes).
    """
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    from repro.core import qlinear as _ql
    _ql.record_matmul("attn_scores", "activation", sq, k.shape[2], d,
                      count=b * hq, act_act=True)
    _ql.record_matmul("attn_pv", "activation", sq, d, k.shape[2],
                      count=b * hq, act_act=True)
    if hq != hkv:
        assert hq % hkv == 0
        rep = hq // hkv
        from repro.distributed import ctx as _ctx
        k = _ctx.heads(jnp.repeat(k, rep, axis=1))
        v = _ctx.heads(jnp.repeat(v, rep, axis=1))
    use_pallas, interp = _use_pallas(force)
    if use_pallas and sq >= 8:
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale, interpret=interp)
    if scale is None:
        scale = d ** -0.5
    chunk = ATTN_CHUNK if q_chunk is None else q_chunk
    if chunk and sq > chunk and sq % chunk == 0:
        return _chunked_attention(q, k, v, causal=causal, window=window,
                                  scale=scale, q_chunk=chunk)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   scale=scale)


def paged_prefill_attention(q, k_new, v_new, k_pool, v_pool, block_table,
                            pos0, *, window: int | None = None,
                            scale: float | None = None,
                            force: Force = "auto",
                            k_scale_pool=None, v_scale_pool=None):
    """Fused paged prefill of one chunk for one slot (see
    ``kernels.flash_prefill``): writes the chunk's KV into its
    destination blocks and attends all T queries in one program.

    q: (T, Hkv, G, hd); k_new/v_new: (T, Hkv, hd) unquantized; pools:
    (NB, Hkv, bs, hd); block_table: (MB,) int32; pos0: scalar int32.
    Returns ``(out, k_pool', v_pool')``.

    With ``k_scale_pool``/``v_scale_pool`` given, the pools are Q8_0
    (int8 quants + fp16 per-32 scales): dispatches the quantized sibling
    kernel, which requantizes the chunk in-kernel, and returns the
    5-tuple ``(out, kq', vq', ks', vs')``.
    """
    use_pallas, interp = _use_pallas(force)
    if k_scale_pool is not None:
        if use_pallas:
            return _fp.flash_prefill_paged_q8(
                q, k_new, v_new, k_pool, v_pool, k_scale_pool,
                v_scale_pool, block_table, pos0, scale=scale,
                window=window, interpret=interp)
        return _fp.flash_prefill_paged_q8_ref(
            q, k_new, v_new, k_pool, v_pool, k_scale_pool, v_scale_pool,
            block_table, pos0, scale=scale, window=window)
    if use_pallas:
        return _fp.flash_prefill_paged(q, k_new, v_new, k_pool, v_pool,
                                       block_table, pos0, scale=scale,
                                       window=window, interpret=interp)
    return _fp.flash_prefill_paged_ref(q, k_new, v_new, k_pool, v_pool,
                                       block_table, pos0, scale=scale,
                                       window=window)
