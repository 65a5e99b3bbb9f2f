"""Plain float32 reference of the SD v1.5 text-to-image pipeline as run.

Written from the architecture's equations in ``jax.numpy``, with every
matmul and convolution at ``Precision.HIGHEST``, no kernels, no im2col,
no batching across requests and no padding steps.  It imports nothing of
the program.  It walks the parameter tree by its key names (``downs``,
``res``, ``conv1``, ...), which are the model file's tensor names, and
takes the weights as plain arrays made by the benchmark from the seed:

* ``{"w": (N, K), "b": (N,) | None, "role": str}`` for a linear layer;
* ``{"w": (N, K), "b": (N,), "role": "conv"}`` for a convolution,
  ``K`` = C x kh x kw in (C, kh, kw) order (a square kernel: its size
  follows from K and the input's channels), a 3x3 convolution padded
  by 1;
* ``{"g", "b"}`` for a norm.

The weights are first put through the configuration's model-file
quantization (:func:`file_weights`): per role the format of
``weight_formats`` (Q8_0, Q3_K, f16, f32, or bf16 kept), quantized as the
model file is written and dequantized exactly to float32.  The
departures of the configuration from the published SD v1.5 (listed under
``assumed`` in its file) are followed, since they are the model as run.

:func:`control_weights` and ``Net("lower")`` give the control: the
same pipeline with every weight one format below the configuration's
and every matmul input rounded to int8 blocks, the precision a later
change might be tempted to take.  ``Net("bf16")`` computes at the
precision the configuration states (every matmul input, activation or
weight, rounded to bfloat16): the error a faithful implementation is
expected to show, a yardstick for cells whose error depends on the
seed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F16_MAX = 65504.0
F16_TINY = 2.0 ** -24


# ------------------------------------------------- model-file formats

def _f16_scale(amax, qmax):
    d = amax / qmax
    d = jnp.where(amax > 0, jnp.clip(d, F16_TINY, F16_MAX), 0.0)
    return d.astype(jnp.float16).astype(jnp.float32)


def _blocks(w, size):
    return w.reshape(*w.shape[:-1], w.shape[-1] // size, size)


def q8_0(w):
    """GGML Q8_0: blocks of 32, f16 scale amax/127, int8 codes."""
    b = _blocks(w, 32)
    d = _f16_scale(jnp.max(jnp.abs(b), -1), 127.0)
    inv = jnp.where(d > 0, 1.0 / d, 0.0)
    q = jnp.clip(jnp.round(b * inv[..., None]), -127, 127)
    return (q * d[..., None]).reshape(w.shape)


def q4_0(w):
    """Q4_0: blocks of 32, f16 scale amax/7, codes in [-8, 7]."""
    b = _blocks(w, 32)
    d = _f16_scale(jnp.max(jnp.abs(b), -1), 7.0)
    inv = jnp.where(d > 0, 1.0 / d, 0.0)
    q = jnp.clip(jnp.round(b * inv[..., None]), -8, 7)
    return (q * d[..., None]).reshape(w.shape)


def _k_quant(w, qmin, qmax):
    """K-quant super-blocks of 256 in 16 sub-blocks of 16: sub-block
    scale amax/|qmin| coded in 6 bits (offset 32) against an f16
    super-scale, codes in [qmin, qmax]."""
    s = w.reshape(*w.shape[:-1], w.shape[-1] // 256, 16, 16)
    d_sub = jnp.max(jnp.abs(s), -1) / float(-qmin)
    d = jnp.max(d_sub, -1) / 31.0
    inv_d = jnp.where(d > 0, 1.0 / d, 0.0)
    code = jnp.clip(jnp.round(d_sub * inv_d[..., None]), 0, 31)
    eff = d[..., None] * code
    inv_eff = jnp.where(eff != 0, 1.0 / eff, 0.0)
    q = jnp.clip(jnp.round(s * inv_eff[..., None]), qmin, qmax)
    d16 = d.astype(jnp.float16).astype(jnp.float32)
    return (q * (d16[..., None] * code)[..., None]).reshape(w.shape)


def q3_k(w):
    """Q3_K: 3-bit codes in [-4, 3]."""
    return _k_quant(w, -4, 3)


def q2_k(w):
    """The same k-quant with 2-bit codes in [-2, 1] (control only)."""
    return _k_quant(w, -2, 1)


def _stored(w, fmt):
    """Float32 values of ``w`` as the model file stores it in ``fmt``.
    A quantized format whose block does not divide K keeps the weight
    as it is, as GGML does."""
    w = w.astype(jnp.float32)
    block = {"q8_0": 32, "q4_0": 32, "q3_k": 256, "q2_k": 256}.get(fmt)
    if block and w.shape[-1] % block:
        return w
    if fmt in ("bf16", "f32"):
        return w.astype(jnp.bfloat16 if fmt == "bf16" else jnp.float32
                        ).astype(jnp.float32)
    if fmt == "f16":
        return w.astype(jnp.float16).astype(jnp.float32)
    return {"q8_0": q8_0, "q4_0": q4_0, "q3_k": q3_k, "q2_k": q2_k}[fmt](w)


# One format below each format the configurations state.
LOWER = {"q8_0": "q4_0", "q3_k": "q2_k", "f16": "q8_0", "f32": "bf16",
         "bf16": "q8_0"}


_stored_jit = jax.jit(_stored, static_argnums=1)


def _map_weights(tree, fmt_of):
    """Arrays only: a layer's ``role`` picks its format and is dropped."""
    def visit(node):
        if isinstance(node, dict) and "role" in node:
            return {"w": _stored_jit(node["w"], fmt_of(node["role"])),
                    "b": None if node["b"] is None
                    else node["b"].astype(jnp.float32)}
        if isinstance(node, dict):
            return {k: visit(v) for k, v in node.items()}
        if isinstance(node, list):
            return [visit(v) for v in node]
        if node is None:
            return None
        return node.astype(jnp.float32)
    return visit(tree)


def _fmt(formats, role):
    return formats.get(role, formats["default"])


def file_weights(tree, formats: dict):
    """The float32 weights of the configuration's model file."""
    return _map_weights(tree, lambda role: _fmt(formats, role))


def control_weights(tree, formats: dict):
    """Every weight one format below the configuration's."""
    return _map_weights(tree, lambda role: LOWER[_fmt(formats, role)])


# ------------------------------------------------------------ layers

def _round_act(x):
    """int8 activation blocks of 32 along K (control only)."""
    k = x.shape[-1]
    pad = -k % 32
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    b = _blocks(xp, 32)
    d = jnp.max(jnp.abs(b), -1, keepdims=True) / 127.0
    inv = jnp.where(d > 0, 1.0 / d, 0.0)
    return (jnp.round(b * inv) * d).reshape(xp.shape)[..., :k]


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


class Net:
    """Layer primitives at a precision: ``"f32"`` (the reference),
    ``"bf16"`` (every matmul input rounded to bfloat16) or ``"lower"``
    (every matmul activation input rounded to int8 blocks)."""

    def __init__(self, mode: str = "f32"):
        assert mode in ("f32", "bf16", "lower")
        self.mode = mode

    def act(self, x):
        if self.mode == "lower":
            return _round_act(x)
        return _bf16(x) if self.mode == "bf16" else x

    def weight(self, w):
        return _bf16(w) if self.mode == "bf16" else w

    def linear(self, p, x):
        y = jnp.einsum("...k,nk->...n", self.act(x), self.weight(p["w"]),
                       precision=HI)
        return y if p["b"] is None else y + p["b"]

    def conv(self, p, x, stride: int = 1):
        cin = x.shape[-1]
        k = math.isqrt(p["w"].shape[1] // cin)
        w = self.weight(p["w"]).reshape(-1, cin, k, k).transpose(2, 3, 1, 0)
        pad = (k - 1) // 2
        y = jax.lax.conv_general_dilated(
            self.act(x), w, (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
        return y + p["b"]

    def attention(self, q, k, v, heads: int, causal: bool = False,
                  q_chunk: int = 1024):
        """softmax(q k^T / sqrt(d)) v over ``heads``; queries in chunks
        so that the score matrix of a 64x64 latent fits."""
        b, sq, c = q.shape
        hd = c // heads

        def split(t):
            return t.reshape(b, -1, heads, hd).transpose(0, 2, 1, 3)
        q, k, v = (self.act(split(t)) for t in (q, k, v))
        sk = k.shape[2]
        outs = []
        for s0 in range(0, sq, q_chunk):
            qc = q[:, :, s0:s0 + q_chunk]
            s = jnp.einsum("bhqd,bhkd->bhqk", qc, k,
                           precision=HI) * hd ** -0.5
            if causal:
                qpos = s0 + jnp.arange(qc.shape[2])[:, None]
                s = jnp.where(jnp.arange(sk)[None, :] <= qpos, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            outs.append(jnp.einsum("bhqk,bhkd->bhqd", self.act(p), v,
                                   precision=HI))
        o = jnp.concatenate(outs, axis=2)
        return o.transpose(0, 2, 1, 3).reshape(b, sq, c)


def group_norm(p, x, groups: int, eps: float = 1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(b, h, w, g, c // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 2, 4), keepdims=True)
    xn = ((xg - mu) / jnp.sqrt(var + eps)).reshape(b, h, w, c)
    return xn * p["g"] + p["b"]


def layer_norm(p, x, eps: float = 1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def gelu(x):
    """GELU, tanh form (as the configuration runs it)."""
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def upsample2(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


# -------------------------------------------------------------- CLIP

def clip_text(net: Net, p, tokens, heads: int):
    """tokens (B, S) -> (B, S, d): token embedding plus sinusoidal
    positions, pre-norm causal transformer layers, final layer norm."""
    emb = p["embed"]["w"]
    x = emb[tokens]
    s, d = tokens.shape[1], emb.shape[1]
    pos = np.arange(s, dtype=np.float64)[:, None]
    inv = 1.0 / 10000.0 ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = pos * inv[None]
    table = np.concatenate([np.sin(ang), np.cos(ang)], -1)
    # The configuration adds the position table in bfloat16.
    x = x + jnp.asarray(table, jnp.float32).astype(jnp.bfloat16
                                                   ).astype(jnp.float32)
    stack = p["layers"][0]
    n_layers = stack["norm1"]["g"].shape[0]
    for i in range(n_layers):
        lp = jax.tree.map(lambda a: a[i], stack)
        h = layer_norm(lp["norm1"], x)
        a = lp["attn"]
        o = net.attention(net.linear(a["wq"], h), net.linear(a["wk"], h),
                          net.linear(a["wv"], h), heads, causal=True)
        x = x + net.linear(a["wo"], o)
        h = layer_norm(lp["norm2"], x)
        x = x + net.linear(lp["mlp"]["down"],
                           gelu(net.linear(lp["mlp"]["up"], h)))
    return layer_norm(p["final_norm"], x)


# -------------------------------------------------------------- UNet

def time_embedding(t, dim: int):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], -1)


def res_block(net, p, x, temb, groups):
    h = net.conv(p["conv1"], silu(group_norm(p["norm1"], x, groups)))
    if temb is not None:
        h = h + net.linear(p["time"], silu(temb))[:, None, None, :]
    h = net.conv(p["conv2"], silu(group_norm(p["norm2"], h, groups)))
    return (net.conv(p["skip"], x) if "skip" in p else x) + h


def spatial_transformer(net, p, x, ctx, heads, groups):
    b, h, w, c = x.shape
    xn = net.conv(p["proj_in"], group_norm(p["norm"], x, groups))
    xn = xn.reshape(b, h * w, c)
    hn = layer_norm(p["ln1"], xn)
    xn = xn + net.linear(p["o1"], net.attention(
        net.linear(p["q1"], hn), net.linear(p["k1"], hn),
        net.linear(p["v1"], hn), heads))
    hn = layer_norm(p["ln2"], xn)
    xn = xn + net.linear(p["o2"], net.attention(
        net.linear(p["q2"], hn), net.linear(p["k2"], ctx),
        net.linear(p["v2"], ctx), heads))
    hg = net.linear(p["ff1"], layer_norm(p["ln3"], xn))
    hh, gate = jnp.split(hg, 2, axis=-1)
    xn = xn + net.linear(p["ff2"], hh * gelu(gate))
    xn = net.conv(p["proj_out"], xn.reshape(b, h, w, c))
    return x + xn


def unet(net: Net, p, x, t, ctx, *, model_channels, heads, groups):
    """eps prediction: x (B, H, W, 4), t (B,), ctx (B, 77, 768)."""
    temb = net.linear(p["time2"], silu(net.linear(
        p["time1"], time_embedding(t, model_channels))))
    h = net.conv(p["conv_in"], x)
    skips = [h]
    for blk in p["downs"]:
        if "down" in blk:
            h = net.conv(blk["down"], h, stride=2)
        else:
            h = res_block(net, blk["res"], h, temb, groups)
            if "attn" in blk:
                h = spatial_transformer(net, blk["attn"], h, ctx, heads,
                                        groups)
        skips.append(h)
    mid = p["mid"]
    h = res_block(net, mid["res1"], h, temb, groups)
    h = spatial_transformer(net, mid["attn"], h, ctx, heads, groups)
    h = res_block(net, mid["res2"], h, temb, groups)
    for blk in p["ups"]:
        h = jnp.concatenate([h, skips.pop()], axis=-1)
        h = res_block(net, blk["res"], h, temb, groups)
        if "attn" in blk:
            h = spatial_transformer(net, blk["attn"], h, ctx, heads, groups)
        if "up" in blk:
            h = net.conv(blk["up"], upsample2(h))
    h = silu(group_norm(p["norm_out"], h, groups))
    return net.conv(p["conv_out"], h)


# --------------------------------------------------------------- VAE

def vae_decode(net: Net, p, z, *, groups, scale_factor):
    """Latent (B, h, w, 4) -> image (B, 8h, 8w, 3) in [-1, 1]."""
    h = net.conv(p["conv_in"], z / scale_factor)
    h = res_block(net, p["mid_res1"], h, None, groups)
    b, hh, ww, c = h.shape
    xn = group_norm(p["mid_norm"], h, groups).reshape(b, hh * ww, c)
    q, k, v = jnp.split(net.linear(p["mid_qkv"], xn), 3, axis=-1)
    h = h + net.linear(p["mid_proj"], net.attention(q, k, v, 1)
                       ).reshape(b, hh, ww, c)
    h = res_block(net, p["mid_res2"], h, None, groups)
    for blk in p["ups"]:
        for r in blk["res"]:
            h = res_block(net, r, h, None, groups)
        if blk["up"] is not None:
            h = net.conv(blk["up"], upsample2(h))
    h = silu(group_norm(p["norm_out"], h, groups))
    return jnp.tanh(net.conv(p["conv_out"], h))


# ---------------------------------------------------------- sampling

def alphas_cumprod(n: int = 1000, beta_start: float = 0.00085,
                   beta_end: float = 0.012) -> np.ndarray:
    """SD's scaled-linear schedule."""
    betas = np.linspace(beta_start ** 0.5, beta_end ** 0.5, n,
                        dtype=np.float64) ** 2
    return np.cumprod(1.0 - betas)


def euler_plan(steps: int):
    """Timesteps and sigmas of the Euler (VE) sampler: ``steps``
    timesteps evenly from 999 to 0, rounded; sigmas appended with 0."""
    ac = alphas_cumprod()
    sig_all = np.sqrt((1.0 - ac) / ac)
    ts = np.round(np.linspace(999, 0, steps)).astype(np.int32)
    return ts, np.concatenate([sig_all[ts], [0.0]])


def generate(net: Net, p, spec: dict, tokens, neg_tokens, guidance: float,
             noise, sampler: str, steps: int):
    """One request: prompt tokens (S,), negative tokens (S,) or None,
    unit noise (h, w, 4) -> image (8h, 8w, 3)."""
    u, v, c = spec["unet"], spec["vae"], spec["text_encoder"]
    use_cfg = neg_tokens is not None or guidance != 1.0
    toks = tokens[None]
    if use_cfg:
        neg = (jnp.zeros_like(tokens) if neg_tokens is None else neg_tokens)
        toks = jnp.stack([tokens, neg])
    ctx = clip_text(net, p["clip"], toks, c["num_attention_heads"])

    def eps_at(xm, t):
        b = ctx.shape[0]
        e = unet(net, p["unet"], jnp.broadcast_to(xm, (b, *xm.shape[1:])),
                 jnp.full((b,), t, jnp.int32), ctx,
                 model_channels=u["block_out_channels"][0],
                 heads=u["num_heads"], groups=u["norm_num_groups"])
        if use_cfg:
            return e[1:] + guidance * (e[:1] - e[1:])
        return e

    x = noise[None].astype(jnp.float32)
    ac = alphas_cumprod()
    if sampler == "turbo":
        a = float(ac[999])
        eps = eps_at(x, 999)
        x0 = (x - math.sqrt(1.0 - a) * eps) / math.sqrt(a)
    elif sampler == "euler":
        ts, sig = euler_plan(steps)
        x = x * math.sqrt(1.0 + sig[0] ** 2)

        def body(x, s):
            t, sg, sg_next = s
            eps = eps_at(x / jnp.sqrt(1.0 + sg ** 2), t)
            return x + (sg_next - sg) * eps, None
        x0, _ = jax.lax.scan(body, x, (jnp.asarray(ts), jnp.asarray(
            sig[:-1], jnp.float32), jnp.asarray(sig[1:], jnp.float32)))
    else:
        raise ValueError(f"no reference for sampler {sampler!r}")
    return vae_decode(net, p["vae"], x0, groups=v["norm_num_groups"],
                      scale_factor=v["scaling_factor"])[0]
