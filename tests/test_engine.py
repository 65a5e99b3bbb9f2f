"""Engine API tests: protocol conformance, sampler registry, CFG,
co-batch determinism, compile-cache / trace-count behavior, streaming
previews, per-request latent sizes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.diffusion import schedule as S
from repro.engine import (TINY_SD, Admitted, Cancelled, DiffusionEngine,
                          Engine, Finished, GenerateRequest, PreviewLatent,
                          Progress, build_denoise, get_sampler,
                          init_pipeline, list_samplers, steps_bucket)
from repro.models.transformer import init_lm
from repro.serving.scheduler import ContinuousBatcher
from repro.serving.scheduler import Request as LMRequest

LM_CFG = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                     num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=96,
                     head_dim=16)


@pytest.fixture(scope="module")
def sd_params():
    return init_pipeline(jax.random.PRNGKey(0), TINY_SD)


@pytest.fixture(scope="module")
def toks():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 77), 0, 512)


def f32(x):
    return np.asarray(jnp.asarray(x, jnp.float32))


# ----------------------------------------------------------- protocol
def test_both_engines_satisfy_protocol(sd_params):
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=1)
    assert isinstance(eng, Engine)
    lm = ContinuousBatcher(init_lm(jax.random.PRNGKey(0), LM_CFG), LM_CFG,
                           slots=1, max_len=8)
    assert isinstance(lm, Engine)


def test_lm_request_cursor_is_declared_field():
    """_cursor is a real dataclass field: copies/replays keep it."""
    r = LMRequest(rid=0, prompt=[1, 2, 3])
    assert r._cursor == 0
    assert dataclasses.replace(r)._cursor == 0
    assert "_cursor" in {f.name for f in dataclasses.fields(LMRequest)}


# ----------------------------------------------------------- registry
def test_registry_has_all_paper_samplers():
    assert {"ddim", "euler", "turbo"} <= set(list_samplers())


def test_unknown_sampler_fails_fast(sd_params):
    with pytest.raises(KeyError, match="unknown sampler"):
        get_sampler("dpm++")
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=1)
    with pytest.raises(KeyError):
        eng.submit(GenerateRequest(rid=0, tokens=[0] * 77, sampler="nope"))


def test_steps_bucket_pow2():
    assert [steps_bucket(s) for s in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]


def test_euler_one_step_matches_turbo_x0(sd_params, toks):
    """The orphaned euler_sigmas/euler_step path, wired through the
    registry, must reproduce turbo_step's x0 estimate in one step."""
    sched = S.NoiseSchedule()
    noise = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 8, 4),
                              jnp.float32)
    g = jnp.ones((1,), jnp.float32)
    neg = jnp.zeros_like(toks[:1])
    x0 = {}
    for name in ("turbo", "euler"):
        fn = build_denoise(TINY_SD, name, False, decode=False)
        plan = get_sampler(name).plan(sched, 1, 1)
        x0[name] = f32(fn(sd_params, toks[:1], neg, g, noise, plan))
    np.testing.assert_allclose(x0["euler"], x0["turbo"],
                               atol=1e-3, rtol=1e-3)


# ------------------------------------------------------------- engine
def test_engine_retires_all_requests_across_buckets(sd_params, toks):
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=2)
    mix = [("turbo", 1), ("ddim", 2), ("ddim", 2), ("euler", 2),
           ("ddim", 2)]
    for i, (sampler, steps) in enumerate(mix):
        eng.submit(GenerateRequest(rid=i, tokens=toks[i % 2],
                                   sampler=sampler, steps=steps, seed=i))
    res = eng.run()
    assert sorted(r.rid for r in res) == list(range(5))
    for r in res:
        assert r.image.shape == (16, 16, 3)
        assert bool(jnp.isfinite(r.image.astype(jnp.float32)).all())
        assert r.decode_steps == r.steps and r.prefill_steps == 0
    assert eng.step() == 0          # queue drained


def test_same_seed_bit_identical_alone_vs_cobatched(sd_params, toks):
    req = GenerateRequest(rid=0, tokens=toks[0], sampler="ddim", steps=2,
                          seed=123)
    e1 = DiffusionEngine(sd_params, TINY_SD, max_batch=2)
    e1.submit(req)
    solo = e1.run()[0].image
    e2 = DiffusionEngine(sd_params, TINY_SD, max_batch=2)
    e2.submit(dataclasses.replace(req, rid=5))
    e2.submit(GenerateRequest(rid=6, tokens=toks[1], sampler="ddim",
                              steps=2, seed=999))
    cob = next(r.image for r in e2.run() if r.rid == 5)
    np.testing.assert_array_equal(f32(solo), f32(cob))


def test_compile_cache_no_retrace(sd_params, toks):
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=2)
    eng.submit(GenerateRequest(rid=0, tokens=toks[0], sampler="ddim",
                               steps=3, seed=1))
    eng.run()
    assert eng.traces == 1          # the whole 3-step loop is one trace
    # Same (sampler, steps, shape): cache hit, no retrace.
    eng.submit(GenerateRequest(rid=1, tokens=toks[1], sampler="ddim",
                               steps=3, seed=2))
    eng.run()
    assert eng.traces == 1
    # steps=4 shares the pow2 steps-bucket of 3: still no retrace.
    eng.submit(GenerateRequest(rid=2, tokens=toks[0], sampler="ddim",
                               steps=4, seed=3))
    eng.run()
    assert eng.traces == 1
    # A different sampler compiles exactly once more.
    eng.submit(GenerateRequest(rid=3, tokens=toks[0], sampler="euler",
                               steps=4, seed=4))
    eng.run()
    assert eng.traces == 2


def test_turbo_normalizes_steps(sd_params, toks):
    """Turbo declares fixed_steps=1: a steps=8 turbo request reuses the
    1-step program (no extra compile, no padded UNet evals) and the
    result reports the steps actually run."""
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=1)
    eng.submit(GenerateRequest(rid=0, tokens=toks[0], sampler="turbo",
                               steps=1, seed=1))
    eng.run()
    eng.submit(GenerateRequest(rid=1, tokens=toks[0], sampler="turbo",
                               steps=8, seed=1))
    res = eng.run()
    assert eng.traces == 1
    assert res[-1].steps == 1
    np.testing.assert_array_equal(f32(res[0].image), f32(res[1].image))


def test_per_request_guidance_scale_applies(sd_params, toks):
    """Two co-batched CFG requests differing only in guidance scale
    must produce different images (per-request scale vector works)."""
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=2)
    neg = jnp.zeros((77,), jnp.int32)
    for rid, g in ((0, 1.5), (1, 7.5)):
        eng.submit(GenerateRequest(rid=rid, tokens=toks[0], neg_tokens=neg,
                                   guidance_scale=g, sampler="turbo",
                                   steps=1, seed=42))
    res = eng.run()
    assert eng.traces == 1          # one CFG program, scales batched
    imgs = {r.rid: f32(r.image) for r in res}
    assert np.isfinite(imgs[0]).all() and np.isfinite(imgs[1]).all()
    assert np.abs(imgs[0] - imgs[1]).max() > 1e-4


def test_preview_stream_matches_fused_scan(sd_params, toks):
    """The segmented (preview-streaming) program path must reproduce
    the fused single-scan result, and stream Progress + PreviewLatent
    events at the requested cadence.

    The two paths are different XLA programs (one scan vs encode +
    per-step + decode programs) that fuse the bf16 UNet/VAE math
    differently, so the [-1, 1] images agree to a couple of bf16 ulps
    (2**-6), not bit for bit."""
    for sampler, steps in (("ddim", 3), ("euler", 2)):
        e1 = DiffusionEngine(sd_params, TINY_SD, max_batch=1)
        e1.submit(GenerateRequest(rid=0, tokens=toks[0], sampler=sampler,
                                  steps=steps, seed=5))
        ref = np.asarray(e1.run()[0].image, np.float32)
        e2 = DiffusionEngine(sd_params, TINY_SD, max_batch=1)
        h = e2.submit(GenerateRequest(rid=0, tokens=toks[0],
                                      sampler=sampler, steps=steps,
                                      seed=5, preview_every=1))
        evs = list(h.events())
        np.testing.assert_allclose(
            np.asarray(h.result().image, np.float32), ref,
            atol=2.0 ** -6, rtol=0)
        previews = [e for e in evs if isinstance(e, PreviewLatent)]
        assert [p.step for p in previews] == list(range(1, steps + 1))
        assert all(p.latent.shape == (8, 8, 4) for p in previews)
        prog = [e for e in evs if isinstance(e, Progress)]
        assert [p.step for p in prog] == list(range(1, steps + 1))


def test_preview_cadence_and_final_step_always_previewed(sd_params, toks):
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=1)
    h = eng.submit(GenerateRequest(rid=0, tokens=toks[0], sampler="ddim",
                                   steps=5, seed=1, preview_every=2))
    steps = [e.step for e in h.events() if isinstance(e, PreviewLatent)]
    assert steps == [2, 4, 5]       # every 2nd + the final step


def test_preview_requests_never_cobatch_with_plain(sd_params, toks):
    """preview_every is part of the group key: a plain request and a
    preview request with otherwise identical settings run as separate
    batches, and the plain one keeps its fused-scan program."""
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=2)
    eng.submit(GenerateRequest(rid=0, tokens=toks[0], sampler="ddim",
                               steps=2, seed=1))
    eng.submit(GenerateRequest(rid=1, tokens=toks[1], sampler="ddim",
                               steps=2, seed=2, preview_every=1))
    n1 = eng.step()                 # plain batch runs alone
    assert n1 == 1
    assert not any(isinstance(e, PreviewLatent) for e in eng.bus.log)
    res = eng.run()
    assert sorted(r.rid for r in res) == [0, 1]
    assert any(isinstance(e, PreviewLatent) for e in eng.bus.log)


def test_diffusion_cancel_queued_and_mid_denoise(sd_params, toks):
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=1)
    eng.submit(GenerateRequest(rid=0, tokens=toks[0], sampler="ddim",
                               steps=3, seed=1, preview_every=1))
    h1 = eng.submit(GenerateRequest(rid=1, tokens=toks[1], sampler="ddim",
                                    steps=3, seed=2))
    assert h1.cancel()              # still queued: leaves the queue
    assert h1.state == "CANCELLED"
    eng.step()                      # admit rid 0, first segment
    assert eng.cancel(0)            # mid-denoise: segmented path
    res = eng.run()
    assert res == []                # nobody finished
    assert not eng.has_work()
    for rid in (0, 1):
        evs = [e for e in eng.bus.log if e.rid == rid]
        assert isinstance(evs[-1], Cancelled)
    assert not eng.cancel(7)        # unknown rid


def test_handle_survives_zero_progress_quantum(sd_params, toks):
    """A quantum that progresses 0 requests and emits nothing (here:
    clearing a fully-cancelled segmented batch) must not trip the
    handle's idle guard while queued work remains."""
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=1)
    eng.submit(GenerateRequest(rid=0, tokens=toks[0], sampler="ddim",
                               steps=3, seed=1, preview_every=1))
    eng.step()                      # segmented batch in flight
    assert eng.cancel(0)
    h = eng.submit(GenerateRequest(rid=1, tokens=toks[1], sampler="turbo",
                                   steps=1, seed=2))
    assert h.result().outcome == "finished"   # pumps through the dead batch
    assert h.state == "FINISHED"


def test_bus_compaction_drops_terminal_history(sd_params, toks):
    """compact() frees finished requests' event payloads (previews)
    without skewing later stream consumers."""
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=1)
    eng.submit(GenerateRequest(rid=0, tokens=toks[0], sampler="ddim",
                               steps=3, seed=1, preview_every=1))
    eng.run()
    n = len(eng.bus.log)
    assert eng.bus.compact() == n and not eng.bus.log
    assert isinstance(eng.bus.terminal(0), Finished)    # verdict kept
    h = eng.submit(GenerateRequest(rid=1, tokens=toks[1], sampler="ddim",
                                   steps=2, seed=2, preview_every=1))
    evs = list(h.events())          # cursors are seq-based: no skew
    assert isinstance(evs[0], Admitted)
    assert isinstance(evs[-1], Finished)
    assert all(e.rid == 1 for e in evs)
    # A handle whose terminal was consumed elsewhere (run) and then
    # compacted must terminate cleanly: no events, result intact.
    eng.bus.compact()
    assert list(h.events()) == []
    assert h.result().finished and h.state == "FINISHED"


def test_duplicate_rid_rejected(sd_params, toks):
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=1)
    eng.submit(GenerateRequest(rid=0, tokens=toks[0], steps=1, seed=1))
    with pytest.raises(ValueError, match="duplicate rid"):
        eng.submit(GenerateRequest(rid=0, tokens=toks[1], steps=1,
                                   seed=2))


# --------------------------------------------------- per-request sizes
def test_latent_hw_mixed_sizes_never_cobatch(sd_params, toks):
    """Per-request latent sizes ride the group/compile key as shape
    buckets: a 4- and a 16-latent request run as separate programs
    with correctly sized outputs."""
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=2)
    eng.submit(GenerateRequest(rid=0, tokens=toks[0], sampler="turbo",
                               steps=1, seed=1, latent_hw=4))
    eng.submit(GenerateRequest(rid=1, tokens=toks[1], sampler="turbo",
                               steps=1, seed=2, latent_hw=16))
    assert eng.step() == 1          # sizes must not share a batch
    res = {r.rid: r for r in eng.run()}
    assert res[0].image.shape == (8, 8, 3)      # 2x VAE upsample
    assert res[1].image.shape == (32, 32, 3)
    assert eng.traces == 2          # one program per shape bucket
    admits = {e.rid: e for e in eng.bus.log if isinstance(e, Admitted)}
    assert admits[0].slot == 0 and admits[1].slot == 0


def test_latent_hw_solo_vs_cobatched_bit_identical(sd_params, toks):
    req = GenerateRequest(rid=0, tokens=toks[0], sampler="ddim", steps=2,
                          seed=77, latent_hw=16)
    e1 = DiffusionEngine(sd_params, TINY_SD, max_batch=2)
    e1.submit(req)
    solo = e1.run()[0].image
    e2 = DiffusionEngine(sd_params, TINY_SD, max_batch=2)
    e2.submit(dataclasses.replace(req, rid=5))
    e2.submit(GenerateRequest(rid=6, tokens=toks[1], sampler="ddim",
                              steps=2, seed=99, latent_hw=16))
    cob = next(r.image for r in e2.run() if r.rid == 5)
    np.testing.assert_array_equal(f32(solo), f32(cob))


def test_latent_hw_validated_at_submit(sd_params, toks):
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=1)
    for hw in (5, -4, 0):           # 0 is invalid, not "use default"
        with pytest.raises(ValueError, match="latent_hw"):
            eng.submit(GenerateRequest(rid=hw, tokens=toks[0],
                                       latent_hw=hw))


def test_run_emits_finished_events_for_plain_requests(sd_params, toks):
    """run() compatibility: the drain wrapper still produces the full
    event lifecycle (Admitted then Finished, nothing after)."""
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=2)
    for i in range(3):
        eng.submit(GenerateRequest(rid=i, tokens=toks[i % 2],
                                   sampler="turbo", steps=1, seed=i))
    res = eng.run()
    assert len(res) == 3
    for i in range(3):
        kinds = [type(e).__name__ for e in eng.bus.log if e.rid == i]
        assert kinds == ["Admitted", "Finished"]


def test_guided_and_unguided_programs_agree_at_scale_one(sd_params, toks):
    """gscale=1 reduces CFG to the conditional branch: the guided
    program must match the plain one up to fp reassociation."""
    sched = S.NoiseSchedule()
    noise = jax.random.normal(jax.random.PRNGKey(9), (1, 8, 8, 4),
                              jnp.float32)
    g = jnp.ones((1,), jnp.float32)
    neg = jnp.zeros_like(toks[:1])
    plan = get_sampler("ddim").plan(sched, 2, 2)
    out = [f32(build_denoise(TINY_SD, "ddim", use_cfg, decode=False)(
        sd_params, toks[:1], neg, g, noise, plan))
        for use_cfg in (False, True)]
    np.testing.assert_allclose(out[0], out[1], atol=5e-2, rtol=5e-2)
