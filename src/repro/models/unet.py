"""SD v1.5 / SD-Turbo / SDXL U-Net in JAX.

Faithful to stable-diffusion.cpp's structure: every convolution is a
role-tagged linear whose weight is stored as GGML's im2col ``mul_mat``
operand ``(out, in*k*k)`` and recorded as that product, so it takes
part in the paper's dot-product accounting.  On the device it runs as a
native convolution (1x1 ones as a plain matmul), which reads each input
once instead of building a patch tensor k*k times its size; only a
packed quantized conv weight takes the im2col route.  Attention blocks
are spatial transformers with cross attention to the CLIP text states.

Full-size config matches SD v1.5 (SD-Turbo shares the architecture);
SDXL's differences are config fields named for its ``unet/config.json``
keys: per-level transformer depth (a stack deeper than one block runs as
a ``lax.scan`` over stacked block weights), a fixed channel count per
head, linear ``proj_in``/``proj_out``, and the added embedding of the
pooled text state and the image-size ids.  Tests run reduced configs.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.qlinear import (Linear, apply_linear, init_linear,
                                is_packed, record_matmul)
from repro.kernels import ops
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    in_channels: int = 4
    out_channels: int = 4
    model_channels: int = 320
    channel_mult: tuple = (1, 2, 4, 4)
    num_res_blocks: int = 2
    attention_levels: tuple = (0, 1, 2)   # levels with spatial transformer
    num_heads: int = 8
    context_dim: int = 768                # text states' width
    time_dim_mult: int = 4
    groups: int = 32
    # Spatial transformer blocks per attention level (int: every level);
    # the mid block takes the last level's.
    transformer_layers_per_block: int | tuple = 1
    # Channels per attention head (SDXL: 64); None keeps ``num_heads``
    # at every width.  Diffusers' key of this name holds the head
    # counts, 5/10/20 for SDXL: 64 channels each.
    attention_head_dim: int | None = None
    use_linear_projection: bool = False   # linear proj_in/out, not 1x1 conv
    # Added embedding (SDXL): sinusoid width of each image-size id, and
    # the input width of its MLP (pooled text state + 6 ids' sinusoids).
    addition_time_embed_dim: int = 0
    projection_class_embeddings_input_dim: int = 0

    @property
    def time_dim(self) -> int:
        return self.model_channels * self.time_dim_mult

    def depth(self, lvl: int) -> int:
        d = self.transformer_layers_per_block
        return d if isinstance(d, int) else d[lvl]

    def heads(self, ch: int) -> int:
        if self.attention_head_dim is None:
            return self.num_heads
        return ch // self.attention_head_dim


SD15_UNET = UNetConfig()
TINY_UNET = UNetConfig(model_channels=32, channel_mult=(1, 2),
                       num_res_blocks=1, attention_levels=(0, 1),
                       num_heads=2, context_dim=64, groups=8)
# SDXL base 1.0 (stabilityai/stable-diffusion-xl-base-1.0 unet/config.json).
SDXL_UNET = UNetConfig(channel_mult=(1, 2, 4), attention_levels=(1, 2),
                       context_dim=2048,
                       transformer_layers_per_block=(1, 2, 10),
                       attention_head_dim=64, use_linear_projection=True,
                       addition_time_embed_dim=256,
                       projection_class_embeddings_input_dim=2816)
# SDXL's mechanisms at the CPU tests' size: no attention at level 0,
# depth 1 at level 1, a stacked depth-2 stack at level 2 and mid.
TINY_XL_UNET = UNetConfig(model_channels=16, channel_mult=(1, 2, 2),
                          num_res_blocks=1, attention_levels=(1, 2),
                          context_dim=48, groups=8,
                          transformer_layers_per_block=(0, 1, 2),
                          attention_head_dim=8, use_linear_projection=True,
                          addition_time_embed_dim=8,
                          projection_class_embeddings_input_dim=32 + 6 * 8)


# ---------------------------------------------------------------- conv

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Conv:
    """A conv as a Linear over im2col patches: the weight is ``(out,
    in*k*k)``, features ordered ``(in, kh, kw)``. Kernel size is static
    aux."""
    lin: Linear
    k: int = 3

    def tree_flatten(self):
        return (self.lin,), self.k

    @classmethod
    def tree_unflatten(cls, k, children):
        return cls(children[0], k)


def init_conv(key, in_ch: int, out_ch: int, k: int = 3, *,
              role: str = "conv") -> Conv:
    fan_in = in_ch * k * k
    w = (jax.random.normal(key, (out_ch, fan_in), jnp.float32)
         * fan_in ** -0.5).astype(jnp.bfloat16)
    return Conv(Linear(w, jnp.zeros((out_ch,), jnp.bfloat16), role), k)


def apply_conv(p: Conv, x: jax.Array, stride: int = 1) -> jax.Array:
    """x: (B, H, W, C) -> (B, H', W', out_ch), ``same`` padding.

    A dense weight runs as one native convolution (a 1x1 one as a
    matmul over ``x``) with the dense ``apply_linear``'s numerics: both
    operands in the weight's dtype, f32 accumulation, the result in
    ``x``'s dtype. A packed quantized weight takes the im2col route:
    the patch tensor GGML builds, then the quantized matmul. Either way
    the recorder sees the im2col product (m = B*H'*W', n = out_ch,
    k = C*k*k), and the whole convolution runs under the ``conv`` named
    scope, so a device trace measures every convolution against the
    same work."""
    k, lin = p.k, p.lin
    pad = (k - 1) // 2
    with jax.named_scope("conv"):
        if k == 1:
            return apply_linear(lin, x[:, ::stride, ::stride])
        if is_packed(lin.w):
            patches = jax.lax.conv_general_dilated_patches(
                x, (k, k), (stride, stride), ((pad, pad), (pad, pad)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            return apply_linear(lin, patches)
        w = lin.w
        # (O, C*k*k) -> (O, C, k, k): the patches' feature order.
        y = jax.lax.conv_general_dilated(
            x.astype(w.dtype), w.reshape(w.shape[0], -1, k, k),
            (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "OIHW", "NHWC"),
            preferred_element_type=jnp.float32).astype(x.dtype)
        b, h, wd, n = y.shape
        record_matmul("linear", lin.role, b * h * wd, n, w.shape[1])
        if lin.b is not None:
            y = y + lin.b.astype(y.dtype)
        return y


# ------------------------------------------------------------ groupnorm

def init_groupnorm(ch: int) -> dict:
    return {"g": jnp.ones((ch,), jnp.float32),
            "b": jnp.zeros((ch,), jnp.float32)}


def groupnorm(p: dict, x: jax.Array, groups: int, eps: float = 1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    xf = x.astype(jnp.float32).reshape(b, h, w, g, c // g)
    mu = jnp.mean(xf, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xf, axis=(1, 2, 4), keepdims=True)
    xn = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(b, h, w, c)
    return (xn * p["g"] + p["b"]).astype(x.dtype)


# ------------------------------------------------------------ res block

def init_resblock(key, in_ch: int, out_ch: int, time_dim: int,
                  groups: int) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "norm1": init_groupnorm(in_ch),
        "conv1": init_conv(ks[0], in_ch, out_ch),
        "time": init_linear(ks[1], time_dim, out_ch, role="time_embed",
                            bias=True),
        "norm2": init_groupnorm(out_ch),
        "conv2": init_conv(ks[2], out_ch, out_ch),
    }
    if in_ch != out_ch:
        p["skip"] = init_conv(ks[3], in_ch, out_ch, k=1)
    return p


def apply_resblock(p: dict, x: jax.Array, temb: jax.Array,
                   groups: int) -> jax.Array:
    h = apply_conv(p["conv1"], jax.nn.silu(groupnorm(p["norm1"], x, groups)))
    h = h + apply_linear(p["time"], jax.nn.silu(temb))[:, None, None, :]
    h = apply_conv(p["conv2"], jax.nn.silu(groupnorm(p["norm2"], h, groups)))
    skip = apply_conv(p["skip"], x) if "skip" in p else x
    return skip + h


# ------------------------------------------- spatial transformer block

def _init_block(ks, ch: int, ctx_dim: int) -> dict:
    """One transformer block (self-attention, cross-attention, GEGLU
    feed-forward) from ten keys."""
    return {
        "ln1": L.init_layernorm(ch),
        "q1": init_linear(ks[0], ch, ch, role="attn_qkv"),
        "k1": init_linear(ks[1], ch, ch, role="attn_qkv"),
        "v1": init_linear(ks[2], ch, ch, role="attn_qkv"),
        "o1": init_linear(ks[3], ch, ch, role="attn_out"),
        "ln2": L.init_layernorm(ch),
        "q2": init_linear(ks[4], ch, ch, role="attn_qkv"),
        "k2": init_linear(ks[5], ctx_dim, ch, role="attn_qkv"),
        "v2": init_linear(ks[6], ctx_dim, ch, role="attn_qkv"),
        "o2": init_linear(ks[7], ch, ch, role="attn_out"),
        "ln3": L.init_layernorm(ch),
        "ff1": init_linear(ks[8], ch, ch * 8, role="mlp_up"),
        "ff2": init_linear(ks[9], ch * 4, ch, role="mlp_down"),
    }


def init_spatial_transformer(key, ch: int, cfg: UNetConfig,
                             depth: int = 1) -> dict:
    """Group norm, ``proj_in``, ``depth`` blocks, ``proj_out``.  One
    block's weights sit beside the projections; a deeper stack's are
    stacked under ``blocks``, leading axis ``depth``."""
    ks = jax.random.split(key, 12)
    if cfg.use_linear_projection:
        def proj(k):
            return init_linear(k, ch, ch, role="proj", bias=True)
    else:
        def proj(k):
            return init_conv(k, ch, ch, k=1)
    p = {"norm": init_groupnorm(ch), "proj_in": proj(ks[0]),
         "proj_out": proj(ks[11])}
    if depth == 1:
        p.update(_init_block(ks[1:11], ch, cfg.context_dim))
    else:
        p["blocks"] = jax.vmap(lambda k: _init_block(
            jax.random.split(k, 10), ch, cfg.context_dim))(
                jax.random.split(ks[1], depth))
    return p


def _mha(q_p, k_p, v_p, o_p, x, ctx, heads: int):
    b, n, c = x.shape
    hd = c // heads

    def split(t):
        return t.reshape(b, -1, heads, hd).transpose(0, 2, 1, 3)
    q = split(apply_linear(q_p, x))
    k = split(apply_linear(k_p, ctx))
    v = split(apply_linear(v_p, ctx))
    out = ops.attention(q, k, v, causal=False)
    out = out.transpose(0, 2, 1, 3).reshape(b, n, c)
    return apply_linear(o_p, out)


def _apply_block(p: dict, xn: jax.Array, ctx: jax.Array,
                 heads: int) -> jax.Array:
    xn = xn + _mha(p["q1"], p["k1"], p["v1"], p["o1"],
                   L.layernorm(p["ln1"], xn), L.layernorm(p["ln1"], xn),
                   heads)
    xn = xn + _mha(p["q2"], p["k2"], p["v2"], p["o2"],
                   L.layernorm(p["ln2"], xn), ctx, heads)
    # GEGLU feed-forward.
    hgl = apply_linear(p["ff1"], L.layernorm(p["ln3"], xn))
    hh, gate = jnp.split(hgl, 2, axis=-1)
    return xn + apply_linear(p["ff2"], hh * jax.nn.gelu(gate))


def apply_spatial_transformer(p: dict, x: jax.Array, ctx: jax.Array,
                              cfg: UNetConfig) -> jax.Array:
    """Under the ``xfmr`` named scope."""
    b, h, w, c = x.shape
    heads = cfg.heads(c)
    with jax.named_scope("xfmr"):
        xn = groupnorm(p["norm"], x, cfg.groups)
        if cfg.use_linear_projection:
            xn = apply_linear(p["proj_in"], xn.reshape(b, h * w, c))
        else:
            xn = apply_conv(p["proj_in"], xn).reshape(b, h * w, c)
        if "blocks" in p:
            xn, _ = jax.lax.scan(
                lambda t, bp: (_apply_block(bp, t, ctx, heads), None), xn,
                p["blocks"])
        else:
            xn = _apply_block(p, xn, ctx, heads)
        if cfg.use_linear_projection:
            xn = apply_linear(p["proj_out"], xn).reshape(b, h, w, c)
        else:
            xn = apply_conv(p["proj_out"], xn.reshape(b, h, w, c))
        return x + xn


# ---------------------------------------------------------------- UNet

def timestep_embedding(t: jax.Array, dim: int) -> jax.Array:
    half = dim // 2
    freqs = jnp.exp(-jnp.log(10_000.0)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], -1)


def init_unet(key, cfg: UNetConfig) -> dict:
    ks = iter(jax.random.split(key, 256))
    ch = cfg.model_channels
    p: dict[str, Any] = {
        "time1": init_linear(next(ks), ch, cfg.time_dim, role="time_embed",
                             bias=True),
        "time2": init_linear(next(ks), cfg.time_dim, cfg.time_dim,
                             role="time_embed", bias=True),
        "conv_in": init_conv(next(ks), cfg.in_channels, ch),
    }
    downs = []
    ch_stack = [ch]
    cur = ch
    for lvl, mult in enumerate(cfg.channel_mult):
        out_ch = ch * mult
        for _ in range(cfg.num_res_blocks):
            blk = {"res": init_resblock(next(ks), cur, out_ch,
                                        cfg.time_dim, cfg.groups)}
            if lvl in cfg.attention_levels:
                blk["attn"] = init_spatial_transformer(next(ks), out_ch, cfg,
                                                       cfg.depth(lvl))
            downs.append(blk)
            cur = out_ch
            ch_stack.append(cur)
        if lvl != len(cfg.channel_mult) - 1:
            downs.append({"down": init_conv(next(ks), cur, cur)})
            ch_stack.append(cur)
    p["downs"] = downs

    p["mid"] = {
        "res1": init_resblock(next(ks), cur, cur, cfg.time_dim, cfg.groups),
        "attn": init_spatial_transformer(
            next(ks), cur, cfg, cfg.depth(len(cfg.channel_mult) - 1)),
        "res2": init_resblock(next(ks), cur, cur, cfg.time_dim, cfg.groups),
    }

    ups = []
    for lvl, mult in reversed(list(enumerate(cfg.channel_mult))):
        out_ch = ch * mult
        for i in range(cfg.num_res_blocks + 1):
            skip = ch_stack.pop()
            blk = {"res": init_resblock(next(ks), cur + skip, out_ch,
                                        cfg.time_dim, cfg.groups)}
            if lvl in cfg.attention_levels:
                blk["attn"] = init_spatial_transformer(next(ks), out_ch, cfg,
                                                       cfg.depth(lvl))
            if i == cfg.num_res_blocks and lvl != 0:
                blk["up"] = init_conv(next(ks), out_ch, out_ch)
            ups.append(blk)
            cur = out_ch
    p["ups"] = ups
    p["norm_out"] = init_groupnorm(cur)
    p["conv_out"] = init_conv(next(ks), cur, cfg.out_channels)
    if cfg.addition_time_embed_dim:
        p["add1"] = init_linear(next(ks),
                                cfg.projection_class_embeddings_input_dim,
                                cfg.time_dim, role="time_embed", bias=True)
        p["add2"] = init_linear(next(ks), cfg.time_dim, cfg.time_dim,
                                role="time_embed", bias=True)
    return p


def image_size_ids(b: int, side: int) -> jax.Array:
    """SDXL's six size ids of a ``side`` x ``side`` image generated
    whole: original size, crop corner (0, 0), target size."""
    ids = jnp.asarray([side, side, 0, 0, side, side], jnp.float32)
    return jnp.broadcast_to(ids, (b, 6))


def apply_unet(p: dict, cfg: UNetConfig, x: jax.Array, t: jax.Array,
               ctx: jax.Array, text_embeds: jax.Array | None = None,
               time_ids: jax.Array | None = None) -> jax.Array:
    """x: (B, H, W, 4) latent; t: (B,) timestep; ctx: (B, 77, ctx_dim).
    With the added embedding (SDXL): text_embeds (B, pooled width), the
    pooled text state, and time_ids (B, 6), :func:`image_size_ids`."""
    temb = timestep_embedding(t, cfg.model_channels).astype(x.dtype)
    temb = apply_linear(p["time2"],
                        jax.nn.silu(apply_linear(p["time1"], temb)))
    if cfg.addition_time_embed_dim:
        with jax.named_scope("add_embed"):
            b = x.shape[0]
            ids = timestep_embedding(time_ids.reshape(-1),
                                     cfg.addition_time_embed_dim)
            y = jnp.concatenate([text_embeds.astype(jnp.float32),
                                 ids.reshape(b, -1)], -1).astype(x.dtype)
            temb = temb + apply_linear(
                p["add2"], jax.nn.silu(apply_linear(p["add1"], y)))
    h = apply_conv(p["conv_in"], x)
    skips = [h]
    for blk in p["downs"]:
        if "down" in blk:
            h = apply_conv(blk["down"], h, stride=2)
        else:
            h = apply_resblock(blk["res"], h, temb, cfg.groups)
            if "attn" in blk:
                h = apply_spatial_transformer(blk["attn"], h, ctx, cfg)
        skips.append(h)
    h = apply_resblock(p["mid"]["res1"], h, temb, cfg.groups)
    h = apply_spatial_transformer(p["mid"]["attn"], h, ctx, cfg)
    h = apply_resblock(p["mid"]["res2"], h, temb, cfg.groups)
    for blk in p["ups"]:
        h = jnp.concatenate([h, skips.pop()], axis=-1)
        h = apply_resblock(blk["res"], h, temb, cfg.groups)
        if "attn" in blk:
            h = apply_spatial_transformer(blk["attn"], h, ctx, cfg)
        if "up" in blk:
            b, hh, ww, c = h.shape
            h = jax.image.resize(h, (b, hh * 2, ww * 2, c), "nearest")
            h = apply_conv(blk["up"], h)
    h = jax.nn.silu(groupnorm(p["norm_out"], h, cfg.groups))
    return apply_conv(p["conv_out"], h)
