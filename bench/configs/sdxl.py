"""SDXL-architecture configurations: the bridge between a configuration
file of this family and the program under test, with the functions of
``sd15.py``.  Layout and requests are SD v1.5's; the configuration adds
a second text encoder, per-level transformer depth, 64 channels per
head, linear projections and the added embedding.  At 3.5 billion
weights the float tree (7.1 GB in bfloat16) and its quantized copy
(4.1 GB) do not both fit beside the float tree's own maker, so
:func:`quantize` gives each float weight up as it quantizes it, and
:func:`plain` hands the reference its weights in host memory.
"""
from __future__ import annotations

import os
import sys

import jax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import sd15  # noqa: E402
from sd15 import is_linear, layout, request  # noqa: E402,F401

from repro.core.policy import get_policy  # noqa: E402
from repro.core.qlinear import quantize_linear  # noqa: E402
from repro.engine import SDConfig  # noqa: E402
from repro.models.clip import clip_config  # noqa: E402
from repro.models.unet import UNetConfig  # noqa: E402
from repro.models.vae import VAEConfig  # noqa: E402


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise ValueError(f"sdxl configuration: {what}")


def _clip(c: dict):
    _check(c["intermediate_size"] == 4 * c["hidden_size"],
           "the encoders' feed-forward is 4x their width")
    return clip_config(d_model=c["hidden_size"],
                       layers=c["num_hidden_layers"],
                       heads=c["num_attention_heads"],
                       vocab=c["vocab_size"],
                       max_len=c["max_position_embeddings"])


def program_config(spec: dict) -> SDConfig:
    u, v = spec["unet"], spec["vae"]
    chans = u["block_out_channels"]
    base = chans[0]
    # Diffusers' attention_head_dim holds head counts; every attention
    # level has the same channels per head.
    head_dims = {chans[lvl] // u["attention_head_dim"][lvl]
                 for lvl in u["attention_levels"]}
    _check(len(head_dims) == 1, "one channel count per head")
    (head_dim,) = head_dims
    unet = UNetConfig(
        in_channels=u["in_channels"], out_channels=u["out_channels"],
        model_channels=base, channel_mult=tuple(ch // base for ch in chans),
        num_res_blocks=u["layers_per_block"],
        attention_levels=tuple(u["attention_levels"]),
        context_dim=u["cross_attention_dim"],
        time_dim_mult=u["time_embed_dim"] // base,
        groups=u["norm_num_groups"],
        transformer_layers_per_block=tuple(
            u["transformer_layers_per_block"]),
        attention_head_dim=head_dim,
        use_linear_projection=u["use_linear_projection"],
        addition_time_embed_dim=u["addition_time_embed_dim"],
        projection_class_embeddings_input_dim=u[
            "projection_class_embeddings_input_dim"])
    vbase = v["block_out_channels"][0]
    vae = VAEConfig(
        z_channels=v["latent_channels"], out_channels=v["out_channels"],
        base=vbase,
        channel_mult=tuple(ch // vbase for ch in v["block_out_channels"]),
        num_res_blocks=v["layers_per_block"], groups=v["norm_num_groups"],
        scale_factor=v["scaling_factor"])
    c, g = spec["text_encoder"], spec["text_encoder_2"]
    _check(g["projection_dim"] == g["hidden_size"],
           "text_projection keeps the second encoder's width")
    _check(c["max_position_embeddings"] == g["max_position_embeddings"],
           "both encoders read the same tokens")
    return SDConfig(name=spec["name"], unet=unet, vae=vae, clip=_clip(c),
                    clip_g=_clip(g), clip_skip=spec["clip_skip"],
                    latent_hw=spec["latent_hw"],
                    text_len=c["max_position_embeddings"])


def quantize(tree, policy_name: str):
    """``sd15.quantize`` with each float weight donated to its quantized
    copy: the caller reads the float tree no more."""
    policy = get_policy(policy_name)
    fn = jax.jit(lambda lin: quantize_linear(lin, policy), donate_argnums=0)
    return jax.tree.map(lambda n: fn(n) if is_linear(n) else n, tree,
                        is_leaf=is_linear)


def plain(tree):
    """``sd15.plain``'s dicts in host memory.  The reference makes its
    compact weights from them a layer at a time, so the device never
    holds the bfloat16 tree (6.9 GB at full size) beside them."""
    return jax.device_get(sd15.plain(tree))
