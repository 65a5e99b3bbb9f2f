"""Plain float32 reference of the SDXL base 1.0 text-to-image pipeline as
run, with the interface of ``sd15_reference.py`` (``Net``,
:func:`generate`, :func:`file_weights`, :func:`control_weights`).

Written from the architecture's equations in ``jax.numpy``, every
matmul and convolution at ``Precision.HIGHEST``, with no kernels, no
im2col, no scan over blocks, no chunked decode, no batching across
requests and no padding steps.  It imports nothing of the program; it
takes SD v1.5's primitives (residual block, norms, attention, the VAE
decoder, the Euler plan) from ``sd15_reference.py``.  What SDXL adds:

* text: the same tokens through CLIP ViT-L to its penultimate layer and
  through OpenCLIP bigG, whose penultimate state joins the context
  ``(77, 768 + 1280)`` and whose last layer, final-normed at the EOS
  token (the largest id) and projected, is the pooled state;
* UNet: per-level transformer depth (a stack deeper than one block is
  stored stacked under ``blocks``; its blocks run one after another),
  64 channels per head, linear ``proj_in``/``proj_out``, and the added
  embedding ``lin(silu(lin([pooled, sincos(size ids, 256)])))`` on top
  of the time embedding;
* the size ids of an image generated whole: ``(S, S, 0, 0, S, S)``.

The model file is 3.4 billion weights, too many to hold in float32
beside what the benchmark keeps on the device.  So :func:`file_weights`
keeps each weight as the file stores it: Q8_0 as int8 codes with one
f16 scale per block of 32 (:class:`Blocks`), f16 convolutions as f16;
``Net`` dequantizes a weight to float32 inside the layer that uses it,
tied to the layer's input by an optimization barrier, so that XLA
neither hoists it out of the step loop nor shares it between steps.  The
values are those ``sd15_reference`` computes in float32.
"""
from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import sd15_reference as sd15  # noqa: E402
from sd15_reference import (LOWER, euler_plan, gelu,  # noqa: E402
                            group_norm, layer_norm, res_block, silu,
                            time_embedding, upsample2, vae_decode)

# ------------------------------------------------- model-file formats

# Largest code and smallest code of each block format (blocks of 32).
CODES = {"q8_0": (127, -127), "q4_0": (7, -8)}


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Blocks:
    """A weight as a block format stores it: int8 codes ``q`` (..., N, K)
    and one f16 scale per block of 32 along K, ``d`` (..., N, K / 32)."""
    q: jax.Array
    d: jax.Array

    @property
    def shape(self):
        return self.q.shape

    def dequantize(self):
        b = sd15._blocks(self.q.astype(jnp.float32), 32)
        return (b * self.d.astype(jnp.float32)[..., None]).reshape(
            self.q.shape)

    def tree_flatten(self):
        return (self.q, self.d), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


def _store(w, fmt):
    """``w`` as the model file stores it in ``fmt``; the codes are
    ``sd15_reference.q8_0``'s and ``q4_0``'s.  A block format whose block
    does not divide K keeps the weight as it is, as GGML does."""
    if fmt in CODES:
        if w.shape[-1] % 32:
            return w
        qmax, qmin = CODES[fmt]
        b = sd15._blocks(w.astype(jnp.float32), 32)
        d = sd15._f16_scale(jnp.max(jnp.abs(b), -1), float(qmax))
        inv = jnp.where(d > 0, 1.0 / d, 0.0)
        q = jnp.clip(jnp.round(b * inv[..., None]), qmin, qmax)
        return Blocks(q.astype(jnp.int8).reshape(w.shape),
                      d.astype(jnp.float16))
    return w.astype({"f16": jnp.float16, "bf16": jnp.bfloat16,
                     "f32": jnp.float32}[fmt])


_store_jit = jax.jit(_store, static_argnums=1)


def _map_weights(tree, fmt_of):
    """A layer's ``role`` picks its format and is dropped; biases and
    norms become float32."""
    def visit(node):
        if isinstance(node, dict) and "role" in node:
            return {"w": _store_jit(node["w"], fmt_of(node["role"])),
                    "b": None if node["b"] is None
                    else jnp.asarray(node["b"], jnp.float32)}
        if isinstance(node, dict):
            return {k: visit(v) for k, v in node.items()}
        if isinstance(node, list):
            return [visit(v) for v in node]
        if node is None:
            return None
        return jnp.asarray(node, jnp.float32)
    return visit(tree)


def _fmt(formats, role):
    return formats.get(role, formats["default"])


def file_weights(tree, formats: dict):
    """The weights of the configuration's model file, stored compact."""
    return _map_weights(tree, lambda role: _fmt(formats, role))


def control_weights(tree, formats: dict):
    """Every weight one format below the configuration's."""
    return _map_weights(tree, lambda role: LOWER[_fmt(formats, role)])


class Net(sd15.Net):
    """``sd15_reference.Net`` over compact weights: each is dequantized
    to float32 where its layer's input is ready."""

    def _weight(self, w, x):
        w, x = jax.lax.optimization_barrier((w, x))
        if isinstance(w, Blocks):
            return w.dequantize(), x
        return w.astype(jnp.float32), x

    def linear(self, p, x):
        w, x = self._weight(p["w"], x)
        return super().linear({"w": w, "b": p["b"]}, x)

    def conv(self, p, x, stride: int = 1):
        w, x = self._weight(p["w"], x)
        return super().conv({"w": w, "b": p["b"]}, x, stride)


def _at(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


# -------------------------------------------------------------- text

def clip_states(net: Net, p, tokens, heads: int, skip: int,
                pooled: bool = False):
    """tokens (B, S) -> hidden states (B, S, d) after layer
    ``L - skip + 1`` (no final norm for ``skip`` > 1), and with
    ``pooled`` the projected final-normed last-layer state at each
    prompt's largest id, (B, d).  Token embedding plus the sinusoidal
    table in bfloat16, pre-norm causal layers, tanh GELU."""
    emb = p["embed"]["w"]
    if isinstance(emb, Blocks):
        x = Blocks(emb.q[tokens], emb.d[tokens]).dequantize()
    else:
        x = emb[tokens].astype(jnp.float32)
    s, d = tokens.shape[1], x.shape[-1]
    pos = jnp.arange(s, dtype=jnp.float32)[:, None]
    inv = 1.0 / 10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    table = jnp.concatenate([jnp.sin(pos * inv), jnp.cos(pos * inv)], -1)
    x = x + table.astype(jnp.bfloat16).astype(jnp.float32)
    stack = p["layers"][0]
    n_layers = stack["norm1"]["g"].shape[0]

    def layer(x, i):
        lp = _at(stack, i)
        h = layer_norm(lp["norm1"], x)
        a = lp["attn"]
        o = net.attention(net.linear(a["wq"], h), net.linear(a["wk"], h),
                          net.linear(a["wv"], h), heads, causal=True)
        x = x + net.linear(a["wo"], o)
        h = layer_norm(lp["norm2"], x)
        return x + net.linear(lp["mlp"]["down"],
                              gelu(net.linear(lp["mlp"]["up"], h)))

    n = n_layers - skip + 1
    for i in range(n):
        x = layer(x, i)
    hidden = x if skip > 1 else layer_norm(p["final_norm"], x)
    if not pooled:
        return hidden
    for i in range(n, n_layers):
        x = layer(x, i)
    x = layer_norm(p["final_norm"], x)
    eos = x[jnp.arange(x.shape[0]), jnp.argmax(tokens, -1)]
    return hidden, net.linear(p["text_projection"], eos)


# -------------------------------------------------------------- UNet

def _block(net, p, x, ctx, heads):
    h = layer_norm(p["ln1"], x)
    x = x + net.linear(p["o1"], net.attention(
        net.linear(p["q1"], h), net.linear(p["k1"], h),
        net.linear(p["v1"], h), heads))
    h = layer_norm(p["ln2"], x)
    x = x + net.linear(p["o2"], net.attention(
        net.linear(p["q2"], h), net.linear(p["k2"], ctx),
        net.linear(p["v2"], ctx), heads))
    hg = net.linear(p["ff1"], layer_norm(p["ln3"], x))
    hh, gate = jnp.split(hg, 2, axis=-1)
    return x + net.linear(p["ff2"], hh * gelu(gate))


def spatial_transformer(net, p, x, ctx, head_dim, groups):
    b, h, w, c = x.shape
    xn = net.linear(p["proj_in"],
                    group_norm(p["norm"], x, groups).reshape(b, h * w, c))
    if "blocks" in p:
        depth = p["blocks"]["ln1"]["g"].shape[0]
        blocks = [_at(p["blocks"], i) for i in range(depth)]
    else:
        blocks = [p]
    for bp in blocks:
        xn = _block(net, bp, xn, ctx, c // head_dim)
    return x + net.linear(p["proj_out"], xn).reshape(b, h, w, c)


def unet(net: Net, p, x, t, ctx, pooled, time_ids, *, model_channels,
         head_dim, groups, add_dim):
    """eps prediction: x (B, H, W, 4), t (B,), ctx (B, 77, 2048), pooled
    (B, 1280), time_ids (B, 6)."""
    b = x.shape[0]
    temb = net.linear(p["time2"], silu(net.linear(
        p["time1"], time_embedding(t, model_channels))))
    ids = time_embedding(time_ids.reshape(-1), add_dim).reshape(b, -1)
    temb = temb + net.linear(p["add2"], silu(net.linear(
        p["add1"], jnp.concatenate([pooled, ids], -1))))
    h = net.conv(p["conv_in"], x)
    skips = [h]
    for blk in p["downs"]:
        if "down" in blk:
            h = net.conv(blk["down"], h, stride=2)
        else:
            h = res_block(net, blk["res"], h, temb, groups)
            if "attn" in blk:
                h = spatial_transformer(net, blk["attn"], h, ctx, head_dim,
                                        groups)
        skips.append(h)
    mid = p["mid"]
    h = res_block(net, mid["res1"], h, temb, groups)
    h = spatial_transformer(net, mid["attn"], h, ctx, head_dim, groups)
    h = res_block(net, mid["res2"], h, temb, groups)
    for blk in p["ups"]:
        h = jnp.concatenate([h, skips.pop()], axis=-1)
        h = res_block(net, blk["res"], h, temb, groups)
        if "attn" in blk:
            h = spatial_transformer(net, blk["attn"], h, ctx, head_dim,
                                    groups)
        if "up" in blk:
            h = net.conv(blk["up"], upsample2(h))
    h = silu(group_norm(p["norm_out"], h, groups))
    return net.conv(p["conv_out"], h)


# ---------------------------------------------------------- sampling

def generate(net: Net, p, spec: dict, tokens, neg_tokens, guidance: float,
             noise, sampler: str, steps: int):
    """One request: prompt tokens (S,), negative tokens (S,) or None,
    unit noise (h, w, 4) -> image (8h, 8w, 3), Euler steps."""
    if sampler != "euler":
        raise ValueError(f"no reference for sampler {sampler!r}")
    u, v = spec["unet"], spec["vae"]
    c, g = spec["text_encoder"], spec["text_encoder_2"]
    skip = spec["clip_skip"]
    use_cfg = neg_tokens is not None or guidance != 1.0
    toks = tokens[None]
    if use_cfg:
        neg = (jnp.zeros_like(tokens) if neg_tokens is None else neg_tokens)
        toks = jnp.stack([tokens, neg])
    ctx_l = clip_states(net, p["clip"], toks, c["num_attention_heads"], skip)
    ctx_g, pooled = clip_states(net, p["clip_g"], toks,
                                g["num_attention_heads"], skip, pooled=True)
    ctx = jnp.concatenate([ctx_l, ctx_g], -1)
    b = ctx.shape[0]
    chans = u["block_out_channels"]
    lvl = u["attention_levels"][0]
    side = noise.shape[0] * 2 ** (len(v["block_out_channels"]) - 1)
    time_ids = jnp.broadcast_to(
        jnp.asarray([side, side, 0, 0, side, side], jnp.float32), (b, 6))

    def eps_at(xm, t):
        e = unet(net, p["unet"], jnp.broadcast_to(xm, (b, *xm.shape[1:])),
                 jnp.full((b,), t, jnp.int32), ctx, pooled, time_ids,
                 model_channels=chans[0],
                 head_dim=chans[lvl] // u["attention_head_dim"][lvl],
                 groups=u["norm_num_groups"],
                 add_dim=u["addition_time_embed_dim"])
        if use_cfg:
            return e[1:] + guidance * (e[:1] - e[1:])
        return e

    ts, sig = euler_plan(steps)
    x = noise[None].astype(jnp.float32) * jnp.sqrt(1.0 + sig[0] ** 2)

    def body(x, s):
        t, sg, sg_next = s
        eps = eps_at(x / jnp.sqrt(1.0 + sg ** 2), t)
        return x + (sg_next - sg) * eps, None
    x0, _ = jax.lax.scan(body, x, (jnp.asarray(ts), jnp.asarray(
        sig[:-1], jnp.float32), jnp.asarray(sig[1:], jnp.float32)))
    return vae_decode(net, p["vae"], x0, groups=v["norm_num_groups"],
                      scale_factor=v["scaling_factor"])[0]
