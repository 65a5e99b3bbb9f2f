"""Chip smoke test: SD-Turbo at full width on the TPU through DiffusionEngine.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py            # one chip: serve, retrace, kernel checks
    python chip_smoke.py --chips 4  # four replicas behind FleetManager only

Weights are generated from a fixed seed at SD-Turbo's published widths
(64x64 latent -> 512x512 image, UNet 320 x (1, 2, 4, 4), 12-layer
768-wide CLIP) and quantized with the ``q8_0`` policy: the shapes,
kernels and bytes are the real ones, the image content is not.

One chip serves 4 requests with ``max_batch=2`` — two SD-Turbo requests
(``turbo``, 1 step) and two classifier-free-guidance requests (``ddim``,
4 steps, guidance 7.5, negative prompt) — then serves them again and
times the steady pass.  It checks the images (shape, finite, the second
pass bit-identical to the first), that the second pass traced nothing,
that the fused program carries Pallas kernels (``tpu_custom_call``), and
the Q8_0 matmul kernel against ``ref.q8_matmul_ref`` at every quantized
site shape of the programs it ran.

``--chips 4`` runs only the fleet path: four replicas, each with its
params committed to its own device, serve 8 requests; it checks that
each replica's images sit on its device and match one engine serving
the same requests.

There is no CPU fallback: without a TPU it exits with status 2 before
any work.  A failed check raises and exits non-zero.  The last line of
standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import qlinear, quant  # noqa: E402
from repro.core.policy import get_policy  # noqa: E402
from repro.diffusion.schedule import NoiseSchedule  # noqa: E402
from repro.engine import (SD_TURBO, DiffusionEngine,  # noqa: E402
                          DiffusionEngineConfig, EngineConfig, FleetManager,
                          GenerateRequest, ReplicaSpec, build_denoise,
                          get_sampler, init_pipeline, quantize_pipeline,
                          steps_bucket)
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import compile_cache  # noqa: E402

POLICY = "q8_0"
SEED = 0
MAX_BATCH = 2
# Kernel vs oracle: both dequantize to identical bf16 weights and
# accumulate in f32, so they may differ only by accumulation order.
# The bound is one bf16 rounding of the largest |x| @ |w| row sum.
KERNEL_REL_BOUND = 2.0 ** -8
# Fleet vs one engine: the same compiled program on another chip of the
# same kind; allow at most one bf16 ulp of a [-1, 1] pixel.
FLEET_ATOL = 2.0 ** -7


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def build_params(cfg):
    """Seeded weights at ``cfg``'s widths, quantized under ``POLICY``
    (one jitted set-up program instead of thousands of eager ops)."""
    policy = get_policy(POLICY)
    return jax.jit(lambda k: quantize_pipeline(init_pipeline(k, cfg),
                                               policy))(
        jax.random.PRNGKey(SEED))


def mixed_requests(cfg, rid0: int) -> list[GenerateRequest]:
    """Two SD-Turbo requests and two CFG requests, fixed prompts/seeds."""
    rng = np.random.RandomState(SEED)
    vocab = cfg.clip_cfg().vocab_size
    prompt = rng.randint(0, vocab, cfg.text_len).tolist()
    negative = rng.randint(0, vocab, cfg.text_len).tolist()
    turbo = [GenerateRequest(rid=rid0 + i, tokens=prompt, sampler="turbo",
                             steps=1, seed=10 + i, guidance_scale=1.0)
             for i in range(2)]
    guided = [GenerateRequest(rid=rid0 + 2 + i, tokens=prompt,
                              neg_tokens=negative, sampler="ddim", steps=4,
                              seed=20 + i, guidance_scale=7.5)
              for i in range(2)]
    return turbo + guided


def turbo_requests(cfg, n: int) -> list[GenerateRequest]:
    vocab = cfg.clip_cfg().vocab_size
    prompt = np.random.RandomState(SEED).randint(0, vocab,
                                                 cfg.text_len).tolist()
    return [GenerateRequest(rid=i, tokens=prompt, sampler="turbo", steps=1,
                            seed=100 + i) for i in range(n)]


def engine_config() -> EngineConfig:
    return EngineConfig(diffusion=DiffusionEngineConfig(max_batch=MAX_BATCH))


def timed_pass(engine: DiffusionEngine, reqs) -> tuple[float, dict]:
    """Serve ``reqs`` to completion; wall seconds and rid -> image."""
    n0 = len(engine.finished)
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    engine.run()
    done = engine.finished[n0:]
    jax.block_until_ready([r.image for r in done])
    return time.perf_counter() - t0, {r.rid: r.image for r in done}


def check_images(images: dict, cfg) -> None:
    side = cfg.latent_hw * 2 ** (len(cfg.vae.channel_mult) - 1)
    for rid, im in sorted(images.items()):
        check(im.shape == (side, side, 3),
              f"rid {rid}: image shape {im.shape}")
        check(bool(jnp.isfinite(im).all()), f"rid {rid}: non-finite pixels")
    say(f"images: {len(images)} x {tuple(next(iter(images.values())).shape)}"
        f", every pixel finite")


def lower_fused(params, cfg, sampler: str, steps: int, use_cfg: bool):
    """Lower the fused program the engine serves for this group; returns
    its text and the quantized matmul sites (m, k, n) it traced."""
    policy = get_policy(POLICY)
    qshapes = {leaf.shape for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, quant.Q8_0Tensor))
        if isinstance(leaf, quant.Q8_0Tensor)}
    sites = set()

    def record(name, role, m, n, k, count=1, act_act=False):
        if not act_act and policy.is_quantized(role) and (n, k) in qshapes:
            sites.add((m, k, n))

    b, hw, S = MAX_BATCH, cfg.latent_hw, jax.ShapeDtypeStruct
    plan = get_sampler(sampler).plan(NoiseSchedule(), steps,
                                     steps_bucket(steps))
    qlinear.set_recorder(record)
    try:
        text = jax.jit(build_denoise(cfg, sampler, use_cfg)).lower(
            params, S((b, cfg.text_len), jnp.int32),
            S((b, cfg.text_len), jnp.int32), S((b,), jnp.float32),
            S((b, hw, hw, 4), jnp.float32), plan).as_text()
    finally:
        qlinear.set_recorder(None)
    return text, sites


@jax.jit
def _site_error(x, w):
    """Largest |kernel - oracle| and the largest |x| @ |w| row sum."""
    got = ops.quantized_matmul(x, w, out_dtype=jnp.float32)
    want = ref.q8_matmul_ref(x, w)
    mag = jnp.abs(x.astype(jnp.float32)) @ jnp.abs(
        quant.dequantize_q8_0(w)).T
    return (jnp.max(jnp.abs(got - want)), jnp.max(mag),
            jnp.all(jnp.isfinite(got)))


def check_kernel_sites(params, sites) -> None:
    """``ops.quantized_matmul`` (the Pallas kernel on the TPU) against
    ``ref.q8_matmul_ref`` on one real weight of every site shape."""
    by_shape = {}
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, quant.Q8_0Tensor)):
        if isinstance(leaf, quant.Q8_0Tensor):
            by_shape.setdefault(leaf.shape, leaf)
    key = jax.random.PRNGKey(SEED + 1)
    worst = 0.0
    for m, k, n in sorted(sites):
        key, sub = jax.random.split(key)
        x = jax.random.normal(sub, (m, k), jnp.bfloat16)
        err, mag, finite = jax.device_get(_site_error(x, by_shape[(n, k)]))
        err, bound = float(err), KERNEL_REL_BOUND * float(mag)
        say(f"kernel site m={m} k={k} n={n}: max|pallas-ref| {err!r} "
            f"bound {bound!r}")
        check(bool(finite) and err <= bound,
              f"q8 kernel at m={m} k={k} n={n}: error {err} > {bound}")
        worst = max(worst, err / bound)
    say(f"kernel sites: {len(sites)} shapes, all within the bf16 bound "
        f"(worst error/bound {worst!r})")


def one_chip(cfg) -> None:
    t0 = time.perf_counter()
    params = build_params(cfg)
    jax.block_until_ready(params)
    say(f"setup: {cfg.name} [{POLICY}] params "
        f"{qlinear.param_bytes(params)} bytes, built in "
        f"{time.perf_counter() - t0!r} s")
    engine = DiffusionEngine(params, cfg, config=engine_config())

    first_s, first = timed_pass(engine, mixed_requests(cfg, 0))
    traces = engine.traces
    say(f"compile+first pass: {first_s!r} s for {len(first)} requests "
        f"(traces {traces})")
    steady_s, steady = timed_pass(engine, mixed_requests(cfg, 100))
    new_traces = engine.traces - traces
    say(f"steady pass: {steady_s!r} s for {len(steady)} requests "
        f"(new traces {new_traces})")
    check(new_traces == 0, f"second pass traced {new_traces} programs")
    check_images(first, cfg)
    check_images(steady, cfg)
    for rid, im in first.items():
        check(bool(jnp.array_equal(im, steady[rid + 100])),
              f"rid {rid}: second pass differs from the first")
    say("second pass bit-identical to the first")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")

    sites = set()
    for sampler, steps, use_cfg in (("turbo", 1, False), ("ddim", 4, True)):
        text, s = lower_fused(params, cfg, sampler, steps, use_cfg)
        calls = text.count("tpu_custom_call")
        say(f"fused program {sampler} steps={steps} cfg={use_cfg}: "
            f"tpu_custom_call x{calls}")
        check(calls > 0, f"{sampler} program has no Pallas kernel")
        sites |= s
    check_kernel_sites(params, sites)


def precompile_replicas(fm: FleetManager, cfg, reqs) -> None:
    """Compile every replica's SD-Turbo program concurrently, one per
    chip (an executable is bound to its device, and XLA compiles release
    the GIL); serving then reuses them.  This reaches into the engine's
    compile cache: a smoke-test shortcut that only saves wall time."""
    sbucket = steps_bucket(1)
    plan = get_sampler("turbo").plan(NoiseSchedule(), 1, sbucket)
    lowered = []
    for rep in fm.replicas:
        eng = rep.engine
        fn = eng._compiled("turbo", sbucket, cfg.latent_hw, False)
        lowered.append(fn.lower(eng.params,
                                *eng._pack(reqs[:MAX_BATCH], cfg.latent_hw),
                                plan))
    with ThreadPoolExecutor(len(lowered)) as pool:
        for f in [pool.submit(low.compile) for low in lowered]:
            f.result()


def fleet(cfg, n_chips: int) -> None:
    devices = jax.devices()
    check(len(devices) >= n_chips,
          f"--chips {n_chips} needs {n_chips} devices, have {len(devices)}")
    params = build_params(cfg)
    specs = [ReplicaSpec(f"chip{i}", params=params, model_cfg=cfg,
                         engine="diffusion", config=engine_config())
             for i in range(n_chips)]
    fm = FleetManager(specs)
    reqs = turbo_requests(cfg, 2 * n_chips)
    t0 = time.perf_counter()
    precompile_replicas(fm, cfg, reqs)
    say(f"fleet: {n_chips} replica programs compiled in "
        f"{time.perf_counter() - t0!r} s")
    t0 = time.perf_counter()
    for r in reqs:
        fm.submit(r)
    results = fm.run()
    jax.block_until_ready([r.image for r in results])
    say(f"fleet: {len(results)} requests on {n_chips} replicas in "
        f"{time.perf_counter() - t0!r} s")
    check(len(results) == len(reqs), f"fleet served {len(results)} of "
          f"{len(reqs)}")
    for rep, dev in zip(fm.replicas, devices):
        served = rep.engine.finished
        check(rep.device == dev, f"{rep.spec.name} placed on {rep.device}")
        check(bool(served), f"{rep.spec.name} served nothing")
        for r in served:
            check(r.image.devices() == {dev},
                  f"rid {r.rid} of {rep.spec.name} on {r.image.devices()}")
        say(f"{rep.spec.name}: rids {[r.rid for r in served]} all on {dev}")
    check_images({r.rid: r.image for r in results}, cfg)

    solo = DiffusionEngine(params, cfg, config=engine_config())
    for r in turbo_requests(cfg, 2 * n_chips):
        solo.submit(r)
    want = {r.rid: r.image for r in solo.run()}
    diff = max(float(jnp.max(jnp.abs(jax.device_get(r.image)
                                     - jax.device_get(want[r.rid]))))
               for r in results)
    say(f"fleet vs one engine: max |diff| {diff!r} over {len(results)} "
        f"images")
    check(diff <= FLEET_ATOL, f"fleet images differ from one engine by "
          f"{diff}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the four-replica fleet path")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        sys.exit(2)
    say(f"device: {dev.device_kind} x{len(jax.devices())} | jax "
        f"{jax.__version__} | compile cache {compile_cache.enable()}")
    if args.chips == 1:
        one_chip(SD_TURBO)
    else:
        fleet(SD_TURBO, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
