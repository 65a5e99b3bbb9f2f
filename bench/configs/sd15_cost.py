"""Operations and bytes of the SD v1.5-architecture pipeline, from shapes.

Every matmul site is listed as ``(role, m, n, k, count)``, with
``2 * m * n * k * count`` operations: the UNet's and the VAE's
convolutions (as the im2col products they are: ``k`` = input channels x
kernel area), the linear layers, and the attention products (scores
``(sq, sk, d)`` and values ``(sq, d, sk)``, ``count`` = batch x heads,
role ``"activation"``).  Nothing here imports the program.

Kernel calls (for rooflines) are the sites a kernel executes:

* a quantized matmul runs every linear layer whose role the
  configuration stores in that format and whose K the format's block
  divides;
* flash attention runs every multi-head attention of CLIP and of the
  UNet (not the VAE's single-head bottleneck attention).

The least bytes a call needs are its weights as the model file packs
them, its activations in and out once in bfloat16, and for attention
q, k, v and o once.
"""
from __future__ import annotations

# Packed bytes per weight of each model-file format.
BYTES_PER_WEIGHT = {"q8_0": 34 / 32, "q4_0": 18 / 32, "q3_k": 110 / 256,
                    "f16": 2.0, "bf16": 2.0, "f32": 4.0}
BLOCK = {"q8_0": 32, "q4_0": 32, "q3_k": 256}
ACT_BYTES = 2


def _attn(out, name, b, heads, sq, sk, d):
    out.append((name, "activation", sq, sk, d, b * heads))
    out.append((name, "activation", sq, d, sk, b * heads))


def clip_sites(spec: dict, b: int) -> list[tuple]:
    c = spec["text_encoder"]
    s, d, f = c["max_position_embeddings"], c["hidden_size"], \
        c["intermediate_size"]
    h = c["num_attention_heads"]
    out = []
    for _ in range(c["num_hidden_layers"]):
        for _ in range(3):
            out.append(("clip_qkv", "attn_qkv", b * s, d, d, 1))
        _attn(out, "clip_attn", b, h, s, s, d // h)
        out.append(("clip_o", "attn_out", b * s, d, d, 1))
        out.append(("clip_up", "mlp_up", b * s, f, d, 1))
        out.append(("clip_down", "mlp_down", b * s, d, f, 1))
    return out


def _res(out, b, hw, cin, cout, temb_dim):
    out.append(("conv", "conv", b * hw, cout, cin * 9, 1))
    if temb_dim:
        out.append(("res_time", "time_embed", b, cout, temb_dim, 1))
    out.append(("conv", "conv", b * hw, cout, cout * 9, 1))
    if cin != cout:
        out.append(("conv", "conv", b * hw, cout, cin, 1))


def _transformer(out, b, hw, c, heads, ctx_len, ctx_dim):
    out.append(("proj_in", "conv", b * hw, c, c, 1))
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
    out.append(("proj_out", "conv", b * hw, c, c, 1))


def unet_sites(spec: dict, b: int) -> list[tuple]:
    u = spec["unet"]
    side = spec["latent_hw"]
    chans = u["block_out_channels"]
    base, tdim, heads = chans[0], u["time_embed_dim"], u["num_heads"]
    ctx_len = spec["text_encoder"]["max_position_embeddings"]
    ctx_dim = u["cross_attention_dim"]
    levels, nrb = u["attention_levels"], u["layers_per_block"]
    out = [("time1", "time_embed", b, tdim, base, 1),
           ("time2", "time_embed", b, tdim, tdim, 1),
           ("conv_in", "conv", b * side * side, base, u["in_channels"] * 9,
            1)]
    stack, cur = [base], base
    for lvl, ch in enumerate(chans):
        for _ in range(nrb):
            _res(out, b, side * side, cur, ch, tdim)
            if lvl in levels:
                _transformer(out, b, side * side, ch, heads, ctx_len,
                             ctx_dim)
            cur = ch
            stack.append(cur)
        if lvl != len(chans) - 1:
            side //= 2
            out.append(("down", "conv", b * side * side, cur, cur * 9, 1))
            stack.append(cur)
    _res(out, b, side * side, cur, cur, tdim)
    _transformer(out, b, side * side, cur, heads, ctx_len, ctx_dim)
    _res(out, b, side * side, cur, cur, tdim)
    for lvl in reversed(range(len(chans))):
        ch = chans[lvl]
        for i in range(nrb + 1):
            _res(out, b, side * side, cur + stack.pop(), ch, tdim)
            if lvl in levels:
                _transformer(out, b, side * side, ch, heads, ctx_len,
                             ctx_dim)
            cur = ch
            if i == nrb and lvl != 0:
                side *= 2
                out.append(("up", "conv", b * side * side, cur, cur * 9, 1))
    out.append(("conv_out", "conv", b * side * side, u["out_channels"],
                base * 9, 1))
    return out


def vae_sites(spec: dict, b: int) -> list[tuple]:
    v = spec["vae"]
    side = spec["latent_hw"]
    chans = v["block_out_channels"]
    top = chans[-1]
    out = [("conv_in", "conv", b * side * side, top,
            v["latent_channels"] * 9, 1)]
    hw = side * side
    _res(out, b, hw, top, top, 0)
    out.append(("vae_qkv", "attn_qkv", b * hw, 3 * top, top, 1))
    out.append(("vae_attn", "activation", hw, hw, top, b))
    out.append(("vae_attn", "activation", hw, top, hw, b))
    out.append(("vae_proj", "attn_out", b * hw, top, top, 1))
    _res(out, b, hw, top, top, 0)
    cur = top
    for lvl in reversed(range(len(chans))):
        ch = chans[lvl]
        for i in range(v["layers_per_block"] + 1):
            _res(out, b, side * side, cur, ch, 0)
            cur = ch
        if lvl != 0:
            side *= 2
            out.append(("up", "conv", b * side * side, cur, cur * 9, 1))
    out.append(("conv_out", "conv", b * side * side, v["out_channels"],
                chans[0] * 9, 1))
    return out


def flops(sites) -> float:
    return float(sum(2 * m * n * k * c for _, _, m, n, k, c in sites))


def request_flops(spec: dict, steps: int, use_cfg: bool) -> float:
    """Useful operations of one request: its prompt encodings, ``steps``
    UNet evaluations per guidance branch, one VAE decode."""
    branches = 2 if use_cfg else 1
    return (branches * flops(clip_sites(spec, 1))
            + steps * branches * flops(unet_sites(spec, 1))
            + flops(vae_sites(spec, 1)))


def fmt_of(spec: dict, role: str) -> str:
    f = spec["weight_formats"]
    return f.get(role, f["default"])


def matmul_calls(spec: dict, sites, fmt: str) -> list[tuple]:
    """``(m, n, k, count)`` of the sites a ``fmt`` kernel runs."""
    return [(m, n, k, c) for _, role, m, n, k, c in sites
            if role != "activation" and fmt_of(spec, role) == fmt
            and k % BLOCK[fmt] == 0]


def attention_calls(sites) -> list[tuple]:
    """``(count, sq, sk, d)`` of the multi-head attentions (scores
    sites of CLIP and the UNet)."""
    return [(c, m, n, k) for name, _, m, n, k, c in sites
            if name in ("clip_attn", "self_attn", "cross_attn")][::2]


def matmul_min_seconds(calls, fmt: str, peak_flops: float,
                       peak_bw: float) -> tuple[float, float, float]:
    """Least time of the calls at the chip's peaks: ``(seconds,
    compute seconds, memory seconds)``, each summed per call."""
    tot = comp = mem = 0.0
    for m, n, k, c in calls:
        f = 2.0 * m * n * k * c
        byt = c * (n * k * BYTES_PER_WEIGHT[fmt] + (m * k + m * n)
                   * ACT_BYTES)
        comp += f / peak_flops
        mem += byt / peak_bw
        tot += max(f / peak_flops, byt / peak_bw)
    return tot, comp, mem


def attention_min_seconds(calls, peak_flops: float,
                          peak_bw: float) -> tuple[float, float, float]:
    tot = comp = mem = 0.0
    for c, sq, sk, d in calls:
        f = 4.0 * c * sq * sk * d
        byt = c * (2 * sq * d + 2 * sk * d) * ACT_BYTES
        comp += f / peak_flops
        mem += byt / peak_bw
        tot += max(f / peak_flops, byt / peak_bw)
    return tot, comp, mem
