"""The SDXL configuration family on the CPU: the program's TINY_XL
pipeline and UNet against the plain reference (``sdxl_reference.py``),
and the shape-based counts (``sdxl_cost.py``) against the program's own
matmul recorder, at the tests' size and at the published widths."""
import collections
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sdxl
import sdxl_cost as cost
from perfbench_fixtures import BENCH, load

# TINY_XL's sizes in this family's file format: three levels with
# transformer depth 0/1/2 (a stacked stack at level 2 and mid), 8
# channels per head, linear projections, the added embedding, two
# encoders (16 and 32 wide) on one token array.
TINY = {
    "unet": {"in_channels": 4, "out_channels": 4,
             "block_out_channels": [16, 32, 32], "layers_per_block": 1,
             "attention_levels": [1, 2],
             "transformer_layers_per_block": [0, 1, 2],
             "attention_head_dim": [2, 4, 4], "use_linear_projection": True,
             "cross_attention_dim": 48, "addition_time_embed_dim": 8,
             "projection_class_embeddings_input_dim": 80,
             "norm_num_groups": 8, "time_embed_dim": 64},
    "vae": {"latent_channels": 4, "out_channels": 3,
            "block_out_channels": [32, 64], "layers_per_block": 1,
            "norm_num_groups": 8, "scaling_factor": 0.13025},
    "text_encoder": {"hidden_size": 16, "num_hidden_layers": 3,
                     "num_attention_heads": 2, "intermediate_size": 64,
                     "vocab_size": 512, "max_position_embeddings": 77},
    "text_encoder_2": {"hidden_size": 32, "num_hidden_layers": 3,
                       "num_attention_heads": 2, "intermediate_size": 128,
                       "vocab_size": 512, "max_position_embeddings": 77,
                       "projection_dim": 32},
    "latent_hw": 8,
}
# A 4-step guidance-free request, the cell's kind, at max_batch 2.
MIX = {"loop": "closed", "queue": 2, "max_requests": 2, "max_batch": 2,
       "request": {"sampler": "euler", "steps": 4, "guidance": 1.0,
                   "negative_prompt": False}}
# Image error the program may show against the reference at this size:
# CPU readings of the program 0.0085-0.0088 (bfloat16 activations and
# Q8_0 weights over 4 steps, seeds 11 and 12); the control (weights one
# format down, int8 matmul inputs) reads 0.082-0.089.
IMAGE_LIMIT = 0.03
# apply_unet in float32 with float32 weights against the reference:
# float32 rounding of the same sums in another order (1e-6 here); the
# reference with bfloat16 matmul inputs reads 1e-3 and more.
UNET_F32_LIMIT = 1e-4


def full_spec() -> dict:
    with open(os.path.join(BENCH, "configs", "sdxl-q8_0.json")) as f:
        return json.load(f)


def tiny_spec() -> dict:
    spec = full_spec()
    spec.update(json.loads(json.dumps(TINY)))
    spec["name"] = "tiny-xl"
    return spec


@pytest.fixture(scope="module")
def reference():
    return load(os.path.join(BENCH, "configs", "sdxl_reference.py"),
                "perfbench_sdxl_reference")


@pytest.fixture(scope="module")
def tiny():
    """The tiny configuration, its seeded weights' maker and key."""
    from harness import weights
    spec = tiny_spec()
    cfg = sdxl.program_config(spec)
    make = weights.maker(lambda: sdxl.layout(cfg), sdxl.is_linear)
    return spec, cfg, make, weights.seed_key(11)


def test_tiny_file_gives_the_tiny_xl_preset(tiny):
    from repro.engine.diffusion_engine import TINY_XL
    _, cfg, _, _ = tiny
    assert (cfg.unet, cfg.vae, cfg.clip, cfg.clip_g, cfg.clip_skip) == (
        TINY_XL.unet, TINY_XL.vae, TINY_XL.clip, TINY_XL.clip_g,
        TINY_XL.clip_skip)


def test_engine_images_match_the_reference(tiny, reference):
    """submit()/step() under Q8_0 against the float32 reference on the
    same Q8_0 weights; the control fails the same limit."""
    from harness import check, traffic
    from repro.engine import (DiffusionEngine, DiffusionEngineConfig,
                              EngineConfig)
    spec, cfg, make, key = tiny
    params = jax.jit(lambda k: sdxl.quantize(make(k), "q8_0"))(key)
    engine = DiffusionEngine(params, cfg, config=EngineConfig(
        diffusion=DiffusionEngineConfig(max_batch=2)))
    reqs = traffic.requests(MIX, spec, 11, 2)
    for r in reqs:
        engine.submit(sdxl.request(r))
    got = {res.rid: np.asarray(res.image, np.float32) for res in engine.run()}
    refs = check.reference_images(sdxl, reference, spec, make, key, reqs,
                                  ("f32", "lower"))
    for r in reqs:
        want = refs["f32"][r["rid"]]
        assert got[r["rid"]].shape == (16, 16, 3)
        assert check.rel_err(got[r["rid"]], want) < IMAGE_LIMIT
        assert check.rel_err(refs["lower"][r["rid"]], want) > IMAGE_LIMIT


def test_unet_matches_the_reference_in_f32(tiny, reference):
    """apply_unet's equations (depth 1 and a stacked depth-2 stack, 8
    channels per head, linear projections, the added embedding) in
    float32 against the reference; the reference at bfloat16 matmul
    inputs fails the same limit."""
    from repro.models import unet as unet_mod
    spec, cfg, make, key = tiny
    w = make(key)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), w["unet"])
    b, hw = 2, cfg.latent_hw
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (b, hw, hw, 4), jnp.float32)
    t = jnp.array([999, 250], jnp.int32)
    ctx = jax.random.normal(ks[1], (b, 77, cfg.unet.context_dim))
    pooled = jax.random.normal(ks[2], (b, 32))
    ids = unet_mod.image_size_ids(b, 8 * hw)
    got = jax.jit(lambda p: unet_mod.apply_unet(
        p, cfg.unet, x, t, ctx, text_embeds=pooled, time_ids=ids))(f32)
    plain = reference.file_weights(sdxl.plain({"unet": w["unet"]}),
                                   {"default": "f32"})
    u = spec["unet"]

    def ref(mode):
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p: reference.unet(
                reference.Net(mode), p["unet"], x, t, ctx, pooled, ids,
                model_channels=16, head_dim=8,
                groups=u["norm_num_groups"],
                add_dim=u["addition_time_embed_dim"]))(plain)
    want = ref("f32")

    def err(a):
        a, w_ = np.asarray(a, np.float64), np.asarray(want, np.float64)
        return np.linalg.norm(a - w_) / np.linalg.norm(w_)
    assert err(got) < UNET_F32_LIMIT
    assert err(ref("bf16")) > UNET_F32_LIMIT


def _unrolled_scan(f, init, xs, length=None, **kw):
    """``lax.scan`` as a Python loop, so that the recorder sees every
    iteration of the scanned stacks (it is called at trace time)."""
    carry, ys = init, []
    for i in range(jax.tree.leaves(xs)[0].shape[0]):
        carry, y = f(carry, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    if ys[0] is None:
        return carry, None
    return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)


def _unrolled_map(f, xs):
    return _unrolled_scan(lambda c, x: (c, f(x)), None, xs)[1]


def _recorded(fn, *args):
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


def _ours(sites):
    return collections.Counter((role, m, n, k, c)
                               for _, role, m, n, k, c in sites)


def _program_sites(spec, b, monkeypatch):
    from repro.engine.diffusion_engine import _encode_one
    from repro.models import unet as unet_mod
    from repro.models import vae as vae_mod
    monkeypatch.setattr(jax.lax, "scan", _unrolled_scan)
    cfg = sdxl.program_config(spec)
    lay = sdxl.layout(cfg)
    hw, S = cfg.latent_hw, jax.ShapeDtypeStruct
    pooled = spec["text_encoder_2"]["projection_dim"]
    unet = _recorded(
        lambda p, x, t, c, y: unet_mod.apply_unet(
            p, cfg.unet, x, t, c, text_embeds=y,
            time_ids=unet_mod.image_size_ids(b, 8 * hw)),
        lay["unet"], S((b, hw, hw, 4), jnp.bfloat16), S((b,), jnp.int32),
        S((b, cfg.text_len, cfg.unet.context_dim), jnp.bfloat16),
        S((b, pooled), jnp.bfloat16))
    vae = _recorded(lambda p, z: vae_mod.decode(p, cfg.vae, z),
                    lay["vae"], S((b, hw, hw, 4), jnp.bfloat16))
    clip = _recorded(lambda p, t: _encode_one(p, cfg, t), lay,
                     S((b, cfg.text_len), jnp.int32))
    return unet, vae, clip


@pytest.mark.parametrize("b", [1, 3])
def test_tiny_sites_match_the_recorder(b, monkeypatch):
    spec = tiny_spec()
    unet, vae, clip = _program_sites(spec, b, monkeypatch)
    assert _ours(cost.unet_sites(spec, b)) == unet
    assert _ours(cost.vae_sites(spec, b)) == vae
    assert _ours(cost.clip_sites(spec, b)) == clip


def test_sdxl_widths_match_the_recorder(monkeypatch):
    spec = full_spec()
    unet, vae, clip = _program_sites(spec, 1, monkeypatch)
    assert _ours(cost.unet_sites(spec, 1)) == unet
    assert _ours(cost.vae_sites(spec, 1)) == vae
    assert _ours(cost.clip_sites(spec, 1)) == clip
    # One UNet evaluation at 128x128, one 1024x1024 decode, one prompt.
    assert cost.flops(cost.unet_sites(spec, 1)) / 1e12 == pytest.approx(
        6.761, abs=5e-4)
    assert cost.flops(cost.vae_sites(spec, 1)) / 1e12 == pytest.approx(
        10.470, abs=5e-4)
    assert cost.flops(cost.clip_sites(spec, 1)) / 1e12 == pytest.approx(
        0.1101, abs=5e-5)


def test_chunked_vae_sites_match_the_recorder(monkeypatch):
    """A batch of four 1024x1024 images decodes one image per call: four
    calls of every VAE site, as the Q8_0 kernel's events count them."""
    from repro.models import vae as vae_mod
    monkeypatch.setattr(jax.lax, "map", _unrolled_map)
    spec = full_spec()
    cfg = sdxl.program_config(spec)
    hw = cfg.latent_hw
    vae = _recorded(lambda p, z: vae_mod.decode(p, cfg.vae, z),
                    sdxl.layout(cfg)["vae"],
                    jax.ShapeDtypeStruct((4, hw, hw, 4), jnp.bfloat16))
    assert cost.vae_chunk(spec, 4) == 1
    assert _ours(cost.vae_sites(spec, 4)) == vae
    assert cost.flops(cost.vae_sites(spec, 4)) == pytest.approx(
        4 * cost.flops(cost.vae_sites(spec, 1)))


def test_flash_attention_calls_per_prompt_and_evaluation():
    """What ``harness/layers.per_module`` counts the kernel's events
    against: 11 + 32 encoder layers per prompt, 70 transformer blocks of
    two attentions per UNet evaluation."""
    spec = full_spec()
    assert len(cost.attention_calls(cost.clip_sites(spec, 4))) == 43
    assert len(cost.attention_calls(cost.unet_sites(spec, 4))) == 140


def test_request_flops_counts_branches_and_steps():
    spec = tiny_spec()
    u = cost.flops(cost.unet_sites(spec, 1))
    v = cost.flops(cost.vae_sites(spec, 1))
    c = cost.flops(cost.clip_sites(spec, 1))
    assert cost.request_flops(spec, 4, False) == pytest.approx(c + 4 * u + v)
    assert cost.request_flops(spec, 20, True) == pytest.approx(
        2 * c + 40 * u + v)


def test_kernel_calls_follow_the_quantized_layers():
    """The sites the Q8_0 kernel runs are the linear layers the program
    stores as Q8_0, at the published widths: every matrix of the
    encoders' layers, the projections, the UNet's and the VAE's
    attention and feed-forward weights (not the token embeddings, which
    are gathered, nor the unused LM heads)."""
    from repro.core.quant import Q8_0Tensor
    from repro.engine import init_pipeline
    spec = full_spec()
    cfg = sdxl.program_config(spec)
    q = jax.eval_shape(lambda k: sdxl.quantize(init_pipeline(k, cfg),
                                               "q8_0"),
                       jax.random.PRNGKey(0))

    def stored(tree):
        return {tuple(leaf.shape[-2:]) for leaf in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, Q8_0Tensor))
            if isinstance(leaf, Q8_0Tensor)}
    sites = cost.unet_sites(spec, 1) + cost.vae_sites(spec, 1)
    assert {(n, k) for _, n, k, _ in cost.matmul_calls(
        spec, sites, "q8_0")} == stored([q["unet"], q["vae"]])
    enc = stored([q["clip"]["layers"], q["clip_g"]["layers"],
                  q["clip_g"]["text_projection"]])
    assert {(n, k) for _, n, k, _ in cost.matmul_calls(
        spec, cost.clip_sites(spec, 1), "q8_0")} == enc
