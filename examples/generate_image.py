"""End-to-end image generation through the request-based engine API
(the paper's Fig. 5 workload, served instead of single-shot).

Submits a batch of ``GenerateRequest``s to a ``DiffusionEngine`` —
sampler picked by name from the registry, per-request seeds and
classifier-free-guidance scales — under a chosen quantization policy,
and reports latency, compile (trace) counts, and model bytes.
With ``--preview-every N`` the engine streams an x0-space
``PreviewLatent`` event every N denoise steps (the segmented program
path) and this host loop reports each preview as it lands.
Offline weights are synthetic, so image *content* is noise-like; the
compute graph, quantized kernels, and byte traffic are the real ones.

Run:  PYTHONPATH=src python examples/generate_image.py \
          [--policy q3_k] [--sampler ddim] [--steps 4] \
          [--size tiny|sd15] [--batch 2] [--guidance 7.5] \
          [--preview-every 1]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.core.policy import get_policy
from repro.core.qlinear import param_bytes
from repro.engine import (SD_TURBO, TINY_SD, DiffusionEngine,
                          GenerateRequest, PreviewLatent, default_sampler,
                          init_pipeline, list_samplers, quantize_pipeline)
from repro.launch import compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="q8_0",
                    choices=["none", "q8_0", "q3_k", "q3_k_imax"])
    ap.add_argument("--sampler", default=None, choices=list_samplers(),
                    help="default: turbo for 1 step, ddim otherwise")
    ap.add_argument("--steps", type=int, default=1)
    ap.add_argument("--size", default="tiny", choices=["tiny", "sd15"])
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--guidance", type=float, default=1.0)
    ap.add_argument("--negative-prompt", default=None)
    ap.add_argument("--prompt", default="a lovely cat")  # paper's prompt
    ap.add_argument("--preview-every", type=int, default=0,
                    help="stream an x0 preview every N denoise steps")
    args = ap.parse_args()
    if args.steps < 1:
        ap.error("--steps must be >= 1")
    compile_cache.enable()

    cfg = TINY_SD if args.size == "tiny" else SD_TURBO
    sampler = args.sampler or default_sampler(args.steps)
    key = jax.random.PRNGKey(0)
    t0 = time.time()
    params = init_pipeline(key, cfg)
    t1 = time.time()
    policy = get_policy(args.policy)
    qp = quantize_pipeline(params, policy)
    t2 = time.time()
    print(f"init {t1-t0:.1f}s | quantize({args.policy}) {t2-t1:.1f}s | "
          f"bytes {param_bytes(params)/1e6:.0f} -> {param_bytes(qp)/1e6:.0f} MB")

    # "Tokenize" the prompt deterministically (no tokenizer offline).
    vocab = cfg.clip_cfg().vocab_size

    def tokenize(text):
        return jnp.array([hash((text, i)) % vocab
                          for i in range(cfg.text_len)], jnp.int32)

    toks = tokenize(args.prompt)
    neg = (tokenize(args.negative_prompt)
           if args.negative_prompt is not None else None)

    engine = DiffusionEngine(qp, cfg, max_batch=args.batch)
    for i in range(args.batch):
        engine.submit(GenerateRequest(
            rid=i, tokens=toks, neg_tokens=neg, sampler=sampler,
            steps=args.steps, seed=7 + i, guidance_scale=args.guidance,
            preview_every=args.preview_every))
    t3 = time.time()
    if args.preview_every:
        for e in engine.stream():       # previews land mid-denoise
            if isinstance(e, PreviewLatent):
                lat = e.latent.astype(jnp.float32)
                print(f"  rid={e.rid} preview {e.step}/{e.total}: "
                      f"x0 latent std {float(lat.std()):.4f}")
        results = list(engine.finished)
    else:
        results = engine.run()
    jax.block_until_ready(results[-1].image)
    t4 = time.time()
    # Steady state: same (sampler, steps, shape) key -> no retrace.
    for i in range(args.batch):
        engine.submit(GenerateRequest(
            rid=args.batch + i, tokens=toks, neg_tokens=neg,
            sampler=sampler, steps=args.steps, seed=100 + i,
            guidance_scale=args.guidance,
            preview_every=args.preview_every))
    engine.run()
    jax.block_until_ready(engine.finished[-1].image)
    t5 = time.time()

    img = results[0].image
    print(f"E2E latency [{sampler}]: compile+run {t4-t3:.2f}s, "
          f"steady-state {t5-t4:.2f}s for batch {args.batch} "
          f"({results[0].steps} step(s), {img.shape[0]}x{img.shape[1]}) | "
          f"jit traces: {engine.traces}")
    for r in results:
        im = r.image.astype(jnp.float32)
        assert bool(jnp.isfinite(im).all()), f"NaN image (rid={r.rid})"
        print(f"  rid={r.rid} seed={r.seed}: mean {float(im.mean()):.4f} "
              f"std {float(im.std()):.4f}")


if __name__ == "__main__":
    main()
