"""SD v1.5-architecture configurations: the bridge between a
configuration file of this family and the program under test.

* :func:`program_config` builds the program's ``SDConfig`` from the
  file's sizes;
* :func:`layout` is the program's parameter tree as shapes;
* :func:`plain` turns a tree of the program's layer objects into the
  plain dicts the reference reads;
* :func:`request` turns one generated request into the program's
  ``GenerateRequest``;
* :func:`quantize` runs the program's model-file quantization under the
  configuration's policy, one jitted program per distinct layer shape.
"""
from __future__ import annotations

import jax

from repro.core.policy import get_policy
from repro.core.qlinear import Linear, quantize_linear
from repro.engine import GenerateRequest, SDConfig, init_pipeline
from repro.models.clip import clip_config
from repro.models.unet import Conv, UNetConfig
from repro.models.vae import VAEConfig


def program_config(spec: dict) -> SDConfig:
    u, v, c = spec["unet"], spec["vae"], spec["text_encoder"]
    base = u["block_out_channels"][0]
    unet = UNetConfig(
        in_channels=u["in_channels"], out_channels=u["out_channels"],
        model_channels=base,
        channel_mult=tuple(ch // base for ch in u["block_out_channels"]),
        num_res_blocks=u["layers_per_block"],
        attention_levels=tuple(u["attention_levels"]),
        num_heads=u["num_heads"], context_dim=u["cross_attention_dim"],
        time_dim_mult=u["time_embed_dim"] // base,
        groups=u["norm_num_groups"])
    vbase = v["block_out_channels"][0]
    vae = VAEConfig(
        z_channels=v["latent_channels"], out_channels=v["out_channels"],
        base=vbase,
        channel_mult=tuple(ch // vbase for ch in v["block_out_channels"]),
        num_res_blocks=v["layers_per_block"], groups=v["norm_num_groups"],
        scale_factor=v["scaling_factor"])
    clip = clip_config(d_model=c["hidden_size"],
                       layers=c["num_hidden_layers"],
                       heads=c["num_attention_heads"],
                       vocab=c["vocab_size"],
                       max_len=c["max_position_embeddings"])
    assert c["intermediate_size"] == 4 * c["hidden_size"]
    return SDConfig(name=spec["name"], unet=unet, vae=vae, clip=clip,
                    latent_hw=spec["latent_hw"],
                    text_len=c["max_position_embeddings"])


def is_linear(node) -> bool:
    return isinstance(node, Linear)


def layout(cfg: SDConfig):
    return jax.eval_shape(lambda k: init_pipeline(k, cfg),
                          jax.random.PRNGKey(0))


def plain(tree):
    def visit(node):
        if isinstance(node, Conv):
            return {"w": node.lin.w, "b": node.lin.b, "role": node.lin.role}
        if isinstance(node, Linear):
            return {"w": node.w, "b": node.b, "role": node.role}
        if isinstance(node, dict):
            return {k: visit(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return [visit(v) for v in node]
        return node
    return visit(tree)


def quantize(tree, policy_name: str):
    policy = get_policy(policy_name)
    fn = jax.jit(lambda lin: quantize_linear(lin, policy))
    return jax.tree.map(lambda n: fn(n) if is_linear(n) else n, tree,
                        is_leaf=is_linear)


def request(r: dict) -> GenerateRequest:
    return GenerateRequest(
        rid=r["rid"], tokens=r["tokens"], neg_tokens=r["neg_tokens"],
        guidance_scale=r["guidance"], sampler=r["sampler"],
        steps=r["steps"], seed=r["seed"], latent_hw=r["latent_hw"],
        preview_every=r["preview_every"])
