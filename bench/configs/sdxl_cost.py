"""Operations and bytes of the SDXL-architecture pipeline, from shapes,
with the interface of ``sd15_cost.py`` (whose conventions hold: sites
``(name, role, m, n, k, count)``, ``2 * m * n * k * count`` operations,
convolutions as their im2col products).  What differs from SD v1.5:

* two text encoders on the same prompt: CLIP ViT-L to its penultimate
  layer (11 of 12 layers) and OpenCLIP bigG through all 32 (its
  penultimate state joins the context, its last gives the pooled state
  through ``text_projection``);
* per-level transformer depth, 64 channels per head, linear
  ``proj_in``/``proj_out`` (role ``proj``), a 2048-wide context;
* the added embedding: two ``time_embed`` linears per UNet evaluation.

The VAE is SD v1.5's, run as the program runs it: in chunks of at most
4 x 512 x 512 output pixels (one 1024x1024 image), each chunk a call of
every VAE site.  Flash attention runs every multi-head attention of the
encoders and the UNet (43 per prompt, 140 per UNet evaluation at full
size), not the VAE's.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import sd15_cost  # noqa: E402
from sd15_cost import (ACT_BYTES, BLOCK, BYTES_PER_WEIGHT,  # noqa: E402,F401
                       _attn, _res, attention_calls, attention_min_seconds,
                       flops, fmt_of, matmul_calls, matmul_min_seconds)

# Output pixels of one VAE decode call: SD v1.5's batch of 4 at 512x512.
PIXELS_PER_CALL = 4 * 512 * 512


def _clip_layers(out, b, c: dict, layers: int):
    s, d, f = c["max_position_embeddings"], c["hidden_size"], \
        c["intermediate_size"]
    h = c["num_attention_heads"]
    for _ in range(layers):
        for _ in range(3):
            out.append(("clip_qkv", "attn_qkv", b * s, d, d, 1))
        _attn(out, "clip_attn", b, h, s, s, d // h)
        out.append(("clip_o", "attn_out", b * s, d, d, 1))
        out.append(("clip_up", "mlp_up", b * s, f, d, 1))
        out.append(("clip_down", "mlp_down", b * s, d, f, 1))


def clip_sites(spec: dict, b: int) -> list[tuple]:
    """Both encoders on ``b`` prompts: the first to layer
    ``num_hidden_layers - clip_skip + 1``, the second through its last
    layer, then its pooled state's projection."""
    c, g = spec["text_encoder"], spec["text_encoder_2"]
    out = []
    _clip_layers(out, b, c, c["num_hidden_layers"] - spec["clip_skip"] + 1)
    _clip_layers(out, b, g, g["num_hidden_layers"])
    d = g["hidden_size"]
    out.append(("text_proj", "proj", b, g["projection_dim"], d, 1))
    return out


def _transformer(out, b, hw, c, heads, depth, ctx_len, ctx_dim):
    out.append(("proj_in", "proj", b * hw, c, c, 1))
    for _ in range(depth):
        for _ in range(3):
            out.append(("self_qkv", "attn_qkv", b * hw, c, c, 1))
        _attn(out, "self_attn", b, heads, hw, hw, c // heads)
        out.append(("self_o", "attn_out", b * hw, c, c, 1))
        out.append(("cross_q", "attn_qkv", b * hw, c, c, 1))
        for _ in range(2):
            out.append(("cross_kv", "attn_qkv", b * ctx_len, c, ctx_dim, 1))
        _attn(out, "cross_attn", b, heads, hw, ctx_len, c // heads)
        out.append(("cross_o", "attn_out", b * hw, c, c, 1))
        out.append(("ff_up", "mlp_up", b * hw, 8 * c, c, 1))
        out.append(("ff_down", "mlp_down", b * hw, c, 4 * c, 1))
    out.append(("proj_out", "proj", b * hw, c, c, 1))


def unet_sites(spec: dict, b: int) -> list[tuple]:
    u = spec["unet"]
    side = spec["latent_hw"]
    chans = u["block_out_channels"]
    base, tdim = chans[0], u["time_embed_dim"]
    depth, heads = u["transformer_layers_per_block"], u["attention_head_dim"]
    ctx_len = spec["text_encoder"]["max_position_embeddings"]
    ctx_dim = u["cross_attention_dim"]
    levels, nrb = u["attention_levels"], u["layers_per_block"]
    out = [("time1", "time_embed", b, tdim, base, 1),
           ("time2", "time_embed", b, tdim, tdim, 1),
           ("add1", "time_embed", b, tdim,
            u["projection_class_embeddings_input_dim"], 1),
           ("add2", "time_embed", b, tdim, tdim, 1),
           ("conv_in", "conv", b * side * side, base, u["in_channels"] * 9,
            1)]

    def xfmr(lvl, hw, ch):
        _transformer(out, b, hw, ch, heads[lvl], depth[lvl], ctx_len,
                     ctx_dim)

    stack, cur = [base], base
    for lvl, ch in enumerate(chans):
        for _ in range(nrb):
            _res(out, b, side * side, cur, ch, tdim)
            if lvl in levels:
                xfmr(lvl, side * side, ch)
            cur = ch
            stack.append(cur)
        if lvl != len(chans) - 1:
            side //= 2
            out.append(("down", "conv", b * side * side, cur, cur * 9, 1))
            stack.append(cur)
    _res(out, b, side * side, cur, cur, tdim)
    xfmr(len(chans) - 1, side * side, cur)
    _res(out, b, side * side, cur, cur, tdim)
    for lvl in reversed(range(len(chans))):
        ch = chans[lvl]
        for i in range(nrb + 1):
            _res(out, b, side * side, cur + stack.pop(), ch, tdim)
            if lvl in levels:
                xfmr(lvl, side * side, ch)
            cur = ch
            if i == nrb and lvl != 0:
                side *= 2
                out.append(("up", "conv", b * side * side, cur, cur * 9, 1))
    out.append(("conv_out", "conv", b * side * side, u["out_channels"],
                base * 9, 1))
    return out


def vae_chunk(spec: dict, b: int) -> int:
    """Images per decode call: the most of ``b`` within
    :data:`PIXELS_PER_CALL` (at least one) that divides ``b``."""
    side = spec["latent_hw"] * 2 ** (len(spec["vae"]["block_out_channels"])
                                      - 1)
    n = max(1, min(b, PIXELS_PER_CALL // (side * side)))
    while b % n:
        n -= 1
    return n


def vae_sites(spec: dict, b: int) -> list[tuple]:
    n = vae_chunk(spec, b)
    return sd15_cost.vae_sites(spec, n) * (b // n)


def request_flops(spec: dict, steps: int, use_cfg: bool) -> float:
    """Useful operations of one request: its prompt encodings, ``steps``
    UNet evaluations per guidance branch, one VAE decode."""
    branches = 2 if use_cfg else 1
    return (branches * flops(clip_sites(spec, 1))
            + steps * branches * flops(unet_sites(spec, 1))
            + flops(vae_sites(spec, 1)))
