"""The benchmark's shape-based operation counts against the program's
own matmul recorder (``core.qlinear.set_recorder``), traced abstractly
with ``jax.eval_shape`` (no arrays are made)."""
import collections
import json
import os

import jax
import jax.numpy as jnp
import pytest

import sd15
import sd15_cost as cost
from perfbench_fixtures import BENCH, tiny_spec


def recorded(fn, *args):
    from repro.core import qlinear
    rec = []
    qlinear.set_recorder(lambda **kw: rec.append(kw))
    try:
        jax.eval_shape(fn, *args)
    finally:
        qlinear.set_recorder(None)
    return collections.Counter(
        ("activation" if r["act_act"] else r["role"], r["m"], r["n"],
         r["k"], r["count"]) for r in rec)


def ours(sites):
    return collections.Counter((role, m, n, k, c)
                               for _, role, m, n, k, c in sites)


def program_sites(spec, b):
    from repro.models import clip as clip_mod
    from repro.models import unet as unet_mod
    from repro.models import vae as vae_mod
    cfg = sd15.program_config(spec)
    lay = sd15.layout(cfg)
    hw, S = cfg.latent_hw, jax.ShapeDtypeStruct
    unet = recorded(
        lambda p, x, t, c: unet_mod.apply_unet(p, cfg.unet, x, t, c),
        lay["unet"], S((b, hw, hw, 4), jnp.bfloat16), S((b,), jnp.int32),
        S((b, cfg.text_len, cfg.unet.context_dim), jnp.bfloat16))
    vae = recorded(lambda p, z: vae_mod.apply_vae_decoder(p, cfg.vae, z),
                   lay["vae"], S((b, hw, hw, 4), jnp.bfloat16))
    # CLIP's layers run in a lax.scan: its body is traced (and recorded)
    # once for all layers.
    clip = recorded(lambda p, t: clip_mod.clip_encode(p, cfg.clip_cfg(), t),
                    lay["clip"], S((b, cfg.text_len), jnp.int32))
    clip = collections.Counter({k: v * spec["text_encoder"]
                                ["num_hidden_layers"]
                                for k, v in clip.items()})
    return unet, vae, clip


@pytest.mark.parametrize("b", [1, 3])
def test_tiny_sites_match_the_recorder(b):
    spec = tiny_spec("q8_0")
    unet, vae, clip = program_sites(spec, b)
    assert ours(cost.unet_sites(spec, b)) == unet
    assert ours(cost.vae_sites(spec, b)) == vae
    assert ours(cost.clip_sites(spec, b)) == clip


def test_sd15_widths_match_the_recorder():
    with open(os.path.join(BENCH, "configs", "sd15-q8_0.json")) as f:
        spec = json.load(f)
    unet, vae, clip = program_sites(spec, 1)
    assert ours(cost.unet_sites(spec, 1)) == unet
    assert ours(cost.vae_sites(spec, 1)) == vae
    assert ours(cost.clip_sites(spec, 1)) == clip
    assert cost.flops(cost.unet_sites(spec, 1)) / 1e12 == pytest.approx(
        0.803, abs=5e-4)
    assert cost.flops(cost.vae_sites(spec, 1)) / 1e12 == pytest.approx(
        2.515, abs=5e-4)


def test_request_flops_counts_branches_and_steps():
    spec = tiny_spec("q8_0")
    u = cost.flops(cost.unet_sites(spec, 1))
    v = cost.flops(cost.vae_sites(spec, 1))
    c = cost.flops(cost.clip_sites(spec, 1))
    assert cost.request_flops(spec, 1, False) == pytest.approx(c + u + v)
    assert cost.request_flops(spec, 20, True) == pytest.approx(
        2 * c + 40 * u + v)


@pytest.mark.parametrize("policy,fmt", [("q8_0", "q8_0"), ("q3_k", "q3_k")])
def test_kernel_calls_follow_the_quantized_layers(policy, fmt):
    """The sites a quantized kernel runs are the linear layers the
    program stores in that format (role in the format, K a multiple of
    its block), at the published widths."""
    from repro.core.quant import Q3KTensor, Q8_0Tensor
    from repro.engine import init_pipeline
    with open(os.path.join(BENCH, "configs", f"sd15-{policy}.json")) as f:
        spec = json.load(f)
    cfg = sd15.program_config(spec)
    q = jax.eval_shape(lambda k: sd15.quantize(init_pipeline(k, cfg),
                                               policy),
                       jax.random.PRNGKey(0))
    qt = Q8_0Tensor if fmt == "q8_0" else Q3KTensor
    stored = {tuple(leaf.shape[-2:]) for part in ("unet", "vae")
              for leaf in jax.tree_util.tree_leaves(
                  q[part], is_leaf=lambda x: isinstance(x, qt))
              if isinstance(leaf, qt)}
    sites = cost.unet_sites(spec, 1) + cost.vae_sites(spec, 1)
    assert {(n, k) for _, n, k, _ in cost.matmul_calls(spec, sites, fmt)} \
        == stored
    clip_q = {tuple(leaf.shape[-2:]) for leaf in jax.tree_util.tree_leaves(
        q["clip"]["layers"], is_leaf=lambda x: isinstance(x, qt))
        if isinstance(leaf, qt)}
    assert {(n, k) for _, n, k, _ in cost.matmul_calls(
        spec, cost.clip_sites(spec, 1), fmt)} == clip_q
