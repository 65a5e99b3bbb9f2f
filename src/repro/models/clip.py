"""CLIP-style text encoder (SD v1.5 and SDXL conditioning), reusing the
LM stack."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.qlinear import apply_linear, init_linear
from repro.models import layers as L
from repro.models import transformer as T


def clip_config(*, d_model: int = 768, layers: int = 12, heads: int = 12,
                vocab: int = 49408, max_len: int = 77) -> ModelConfig:
    return ModelConfig(
        name="clip_text", family="dense", num_layers=layers,
        d_model=d_model, num_heads=heads, num_kv_heads=heads,
        d_ff=4 * d_model, vocab_size=vocab, norm="layernorm",
        activation="gelu", pos_embed="sinusoidal")


TINY_CLIP = clip_config(d_model=64, layers=2, heads=2, vocab=512)


def init_clip(key: jax.Array, cfg: ModelConfig, *,
              projection: bool = False) -> dict:
    """``projection``: with OpenCLIP's ``text_projection`` (d to d, no
    bias), which maps the pooled state."""
    p = T.init_lm(key, cfg)
    if projection:
        p["text_projection"] = init_linear(jax.random.fold_in(key, 1),
                                           cfg.d_model, cfg.d_model,
                                           role="proj")
    return p


def clip_encode(params: dict, cfg: ModelConfig, tokens: jax.Array,
                skip: int = 1, pooled: bool = False):
    """tokens: (B, 77) -> hidden states (B, 77, d) (pre-unembed): after
    the last layer and the final layer norm with ``skip`` 1, else after
    layer ``num_layers - skip + 1`` with no norm (SDXL's ``skip`` 2, the
    penultimate layer).  With ``pooled`` also ``(B, d)``: the last
    layer's final-normed state at each prompt's EOS token (its largest
    id) through ``text_projection``."""
    b, s = tokens.shape
    x = L.apply_embedding(params["embed"], tokens)
    x = x + T._sinusoidal(s, cfg.d_model)[None]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                 (b, s))

    def layers(x, lo, hi):
        part = params["layers"]
        if (lo, hi) != (0, cfg.num_layers):
            part = jax.tree.map(lambda a: a[lo:hi], part)
        return T._stack_fwd(part, cfg, x, positions, causal=True)[0]

    n = cfg.num_layers - skip + 1
    x = layers(x, 0, n)
    hidden = x
    if skip == 1:
        hidden = x = T._apply_norm(cfg, params["final_norm"], x)
    if not pooled:
        return hidden
    if skip > 1:
        x = T._apply_norm(cfg, params["final_norm"],
                          layers(x, n, cfg.num_layers))
    eos = x[jnp.arange(b), jnp.argmax(tokens, -1)]
    return hidden, apply_linear(params["text_projection"], eos)
