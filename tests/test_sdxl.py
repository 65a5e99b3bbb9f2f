"""The SDXL fields leave SD v1.5's program as it was, and the VAE's
chunked decode gives the unchunked result.  (The SDXL pipeline itself is
compared with its plain reference in ``bench/tests/test_perfbench_sdxl.py``.)
"""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import get_policy
from repro.diffusion import schedule as sched_mod
from repro.engine import samplers as samplers_mod
from repro.engine.diffusion_engine import (SD_TURBO, SDXL, TINY_SD,
                                           build_denoise, init_pipeline,
                                           quantize_pipeline)
from repro.models import vae as vae_mod

# SHA-256 digests of SD v1.5's parameter tree (paths, shapes, dtypes) at
# its published widths, of its fused denoise program lowered at those
# widths (euler, 1 step, batch 1), and of that program at TINY_SD widths
# (euler, 2 steps, CFG, batch 2) optimized by XLA's CPU compiler with
# metadata and source locations left out, as the code was before SDXL's
# fields came in.  A change to any of them changes what the SD v1.5
# cells run.
SD15_TREE = "5d3f48398ce0bae5351bafca2d27014f8409c89635e9e74593daa0aeaf0aa990"
SD15_LOWERED = \
    "3c59c3baae9e4f0a26dcc209ca90c2af942411475baa3585d3b5d90ddbe49fbf"
TINY_OPTIMIZED = \
    "b685d317ff07d3e5d4691caba6c50817900359d95dda5af70598d080d49af43d"

METADATA = re.compile(r", metadata=\{[^{}]*\}")
SOURCE_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames")


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def _tree_digest(cfg) -> str:
    lay = jax.eval_shape(lambda k: init_pipeline(k, cfg),
                         jax.random.PRNGKey(0))
    return _sha("\n".join(
        f"{jax.tree_util.keystr(p)} {leaf.shape} {leaf.dtype}"
        for p, leaf in jax.tree_util.tree_flatten_with_path(lay)[0]))


def _lowered(cfg, b: int, steps: int, use_cfg: bool):
    fn = build_denoise(cfg, "euler", use_cfg)
    plan = samplers_mod.get_sampler("euler").plan(
        sched_mod.NoiseSchedule(), steps, steps)
    params = jax.eval_shape(
        lambda k: quantize_pipeline(init_pipeline(k, cfg),
                                    get_policy("q8_0")),
        jax.random.PRNGKey(0))
    S, hw = jax.ShapeDtypeStruct, cfg.latent_hw
    return jax.jit(fn).lower(
        params, S((b, cfg.text_len), jnp.int32),
        S((b, cfg.text_len), jnp.int32), S((b,), jnp.float32),
        S((b, hw, hw, 4), jnp.float32), plan)


def _without_debug_info(hlo: str) -> str:
    out, skip = [], False
    for line in METADATA.sub("", hlo).split("\n"):
        if line in SOURCE_TABLES:
            skip = True
        elif skip and line.startswith(("%", "ENTRY")):
            skip = False
        if not skip:
            out.append(line)
    return "\n".join(out)


def test_sd15_tree_is_unchanged():
    assert _tree_digest(SD_TURBO) == SD15_TREE


def test_sd15_program_is_unchanged():
    assert _sha(_lowered(SD_TURBO, 1, 1, False).as_text()) == SD15_LOWERED
    hlo = _lowered(TINY_SD, 2, 2, True).compile().as_text()
    assert _sha(_without_debug_info(hlo)) == TINY_OPTIMIZED


@pytest.mark.parametrize("cfg,batch,want", [
    (SD_TURBO, 4, 4), (SD_TURBO, 1, 1), (SDXL, 4, 1), (SDXL, 1, 1)])
def test_decode_budget_follows_the_image_size(cfg, batch, want):
    """Four 512x512 images per call: SD v1.5's batch of four stays one
    call, SDXL's 1024x1024 images go one at a time."""
    assert vae_mod.images_per_call(cfg.vae, batch, cfg.latent_hw) == want


def test_images_per_call_divides_the_batch(monkeypatch):
    # TINY_SD's VAE doubles the side: 16x16 images from 8x8 latents.
    monkeypatch.setattr(vae_mod, "PIXELS_PER_CALL", 3 * 16 * 16)
    assert vae_mod.images_per_call(TINY_SD.vae, 4, 8) == 2
    assert vae_mod.images_per_call(TINY_SD.vae, 6, 8) == 3


def test_chunked_decode_equals_one_call(monkeypatch):
    """Each image's pixels depend on its own latent only, so decoding two
    at a time gives what one call over the batch gives.  In float32 the
    two differ by rounding alone (XLA may order a batch of 2's sums
    differently from one of 4's); an image put in another's row, or
    decoded twice, would be off by the image's own size."""
    cfg = TINY_SD.vae
    p = jax.jit(lambda k: jax.tree.map(
        lambda a: a.astype(jnp.float32),
        vae_mod.init_vae_decoder(k, cfg)))(jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 8, 4), jnp.float32)
    whole = jax.jit(lambda p, z: vae_mod.apply_vae_decoder(p, cfg, z))(p, z)
    monkeypatch.setattr(vae_mod, "PIXELS_PER_CALL", 2 * 16 * 16)
    assert vae_mod.images_per_call(cfg, 4, 8) == 2
    chunked = jax.jit(lambda p, z: vae_mod.decode(p, cfg, z))(p, z)
    assert chunked.shape == whole.shape == (4, 16, 16, 3)
    got = np.asarray(chunked, np.float64)
    want = np.asarray(whole, np.float64)
    for i in range(4):
        err = np.linalg.norm(got[i] - want[i]) / np.linalg.norm(want[i])
        assert err < 1e-5, (i, err)
