"""SD VAE decoder (latent -> image).

Its convolutions are ``unet.apply_conv``'s: native convolutions on the
device, counted as GGML's im2col ``mul_mat``.  :func:`decode` runs a
batch in chunks of at most :data:`PIXELS_PER_CALL` output pixels, so a
1024x1024 batch holds what SD v1.5's batch of four 512x512 images
holds."""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.unet import (apply_conv, groupnorm, init_conv,
                               init_groupnorm)
from repro.core.qlinear import apply_linear, init_linear


@dataclasses.dataclass(frozen=True)
class VAEConfig:
    z_channels: int = 4
    out_channels: int = 3
    base: int = 128
    channel_mult: tuple = (1, 2, 4, 4)   # decoder runs reversed
    num_res_blocks: int = 2
    groups: int = 32
    scale_factor: float = 0.18215


SD15_VAE = VAEConfig()
SDXL_VAE = VAEConfig(scale_factor=0.13025)

# Output pixels one decode call holds: SD v1.5's batch of 4 at 512x512.
PIXELS_PER_CALL = 4 * 512 * 512
TINY_VAE = VAEConfig(base=32, channel_mult=(1, 2), num_res_blocks=1,
                     groups=8)


def _init_res(key, in_ch, out_ch):
    ks = jax.random.split(key, 3)
    p = {"norm1": init_groupnorm(in_ch), "conv1": init_conv(ks[0], in_ch, out_ch),
         "norm2": init_groupnorm(out_ch), "conv2": init_conv(ks[1], out_ch, out_ch)}
    if in_ch != out_ch:
        p["skip"] = init_conv(ks[2], in_ch, out_ch, k=1)
    return p


def _apply_res(p, x, groups):
    h = apply_conv(p["conv1"], jax.nn.silu(groupnorm(p["norm1"], x, groups)))
    h = apply_conv(p["conv2"], jax.nn.silu(groupnorm(p["norm2"], h, groups)))
    return (apply_conv(p["skip"], x) if "skip" in p else x) + h


def init_vae_decoder(key, cfg: VAEConfig) -> dict:
    ks = iter(jax.random.split(key, 64))
    top = cfg.base * cfg.channel_mult[-1]
    p: dict[str, Any] = {
        "conv_in": init_conv(next(ks), cfg.z_channels, top),
        "mid_res1": _init_res(next(ks), top, top),
        "mid_qkv": init_linear(next(ks), top, 3 * top, role="attn_qkv"),
        "mid_proj": init_linear(next(ks), top, top, role="attn_out"),
        "mid_norm": init_groupnorm(top),
        "mid_res2": _init_res(next(ks), top, top),
    }
    ups = []
    cur = top
    for lvl, mult in reversed(list(enumerate(cfg.channel_mult))):
        out_ch = cfg.base * mult
        blks = [_init_res(next(ks), cur if i == 0 else out_ch, out_ch)
                for i in range(cfg.num_res_blocks + 1)]
        cur = out_ch
        up = init_conv(next(ks), cur, cur) if lvl != 0 else None
        ups.append({"res": blks, "up": up})
    p["ups"] = ups
    p["norm_out"] = init_groupnorm(cur)
    p["conv_out"] = init_conv(next(ks), cur, cfg.out_channels)
    return p


def apply_vae_decoder(p: dict, cfg: VAEConfig, z: jax.Array) -> jax.Array:
    """z: (B, h, w, 4) latent -> (B, 8h, 8w, 3) image in [-1, 1]."""
    h = apply_conv(p["conv_in"], z / cfg.scale_factor)
    h = _apply_res(p["mid_res1"], h, cfg.groups)
    # Single-head spatial self-attention at the bottleneck.
    b, hh, ww, c = h.shape
    xn = groupnorm(p["mid_norm"], h, cfg.groups).reshape(b, hh * ww, c)
    qkv = apply_linear(p["mid_qkv"], xn)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    from repro.core.qlinear import record_matmul
    record_matmul("vae_attn_scores", "activation", hh * ww, hh * ww, c,
                  count=b, act_act=True)
    record_matmul("vae_attn_pv", "activation", hh * ww, c, hh * ww,
                  count=b, act_act=True)
    att = jax.nn.softmax(
        jnp.einsum("bqc,bkc->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * c ** -0.5, -1)
    xn = jnp.einsum("bqk,bkc->bqc", att, v.astype(jnp.float32))
    h = h + apply_linear(p["mid_proj"], xn.astype(h.dtype)).reshape(
        b, hh, ww, c)
    h = _apply_res(p["mid_res2"], h, cfg.groups)
    for blk in p["ups"]:
        for r in blk["res"]:
            h = _apply_res(r, h, cfg.groups)
        if blk["up"] is not None:
            b, hh, ww, c = h.shape
            h = jax.image.resize(h, (b, hh * 2, ww * 2, c), "nearest")
            h = apply_conv(blk["up"], h)
    h = jax.nn.silu(groupnorm(p["norm_out"], h, cfg.groups))
    return jnp.tanh(apply_conv(p["conv_out"], h))


def images_per_call(cfg: VAEConfig, batch: int, latent_hw: int) -> int:
    """Images :func:`decode` decodes per call: the most of ``batch``
    within :data:`PIXELS_PER_CALL` (at least one) that divides it."""
    side = latent_hw * 2 ** (len(cfg.channel_mult) - 1)
    n = max(1, min(batch, PIXELS_PER_CALL // (side * side)))
    while batch % n:
        n -= 1
    return n


def decode(p: dict, cfg: VAEConfig, z: jax.Array) -> jax.Array:
    """:func:`apply_vae_decoder` over ``z`` in chunks of
    :func:`images_per_call` images, one after another (``lax.map``); a
    batch within the budget is one call."""
    b = z.shape[0]
    n = images_per_call(cfg, b, z.shape[1])
    if n == b:
        return apply_vae_decoder(p, cfg, z)
    out = jax.lax.map(lambda c: apply_vae_decoder(p, cfg, c),
                      z.reshape(b // n, n, *z.shape[1:]))
    return out.reshape(b, *out.shape[2:])
