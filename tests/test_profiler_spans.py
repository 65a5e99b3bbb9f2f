"""The program names its parts for the profiler: ``jax.named_scope``
scopes in the denoise programs' op metadata, and ``repro.obs.span``
host spans (with their args) around the engine's host work, on the
profiler's clock and from the same call sites as the phase telemetry.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

from repro.diffusion import schedule as sched_mod
from repro.engine import (TINY_SD, DiffusionEngine, GenerateRequest,
                          init_pipeline)
from repro.engine import samplers as samplers_mod
from repro.engine.diffusion_engine import TINY_XL, build_denoise
from repro.obs import Telemetry, TraceRecorder, span


@pytest.fixture(scope="module")
def sd_params():
    return init_pipeline(jax.random.PRNGKey(0), TINY_SD)


def _req(rid: int, **kw) -> GenerateRequest:
    toks = list(range(TINY_SD.text_len))
    return GenerateRequest(rid=rid, tokens=toks, sampler="euler", steps=3,
                           seed=rid, **kw)


def _host_spans(trace_dir) -> list[tuple]:
    """``(name, start_ns, end_ns, args)`` of the ``engine.*`` host
    events of the one trace under ``trace_dir``."""
    paths = [os.path.join(d, n) for d, _, fs in os.walk(trace_dir)
             for n in fs if n.endswith(".xplane.pb")]
    assert len(paths) == 1
    pd = jax.profiler.ProfileData.from_file(paths[0])
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("engine."):
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


def test_lowered_program_carries_model_scopes():
    """CLIP, each UNet evaluation (inside the scan, both guidance
    branches) and the VAE run under their scopes, and every convolution
    of the UNet and of the VAE under ``conv``."""
    params = jax.eval_shape(lambda: init_pipeline(jax.random.PRNGKey(0),
                                                  TINY_SD))
    b, tl, hw = 2, TINY_SD.text_len, TINY_SD.latent_hw
    plan = samplers_mod.get_sampler("euler").plan(
        sched_mod.NoiseSchedule(), 2, 2)
    spec = jax.ShapeDtypeStruct
    text = jax.jit(build_denoise(TINY_SD, "euler", True)).lower(
        params, spec((b, tl), jnp.int32), spec((b, tl), jnp.int32),
        spec((b,), jnp.float32), spec((b, hw, hw, 4), jnp.float32),
        plan).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*/[^"]*)"', text))
    scoped = {c for p in paths for c in p.split("/")}
    assert {"clip", "unet", "vae", "conv"} <= scoped
    assert any("unet/conv/" in p for p in paths)
    assert any("vae/conv/" in p for p in paths)
    assert any(p.startswith("jit(fn)/clip/") for p in paths)


@pytest.mark.parametrize("cfg,inner", [
    (TINY_SD, {"unet/xfmr/"}),
    (TINY_XL, {"unet/xfmr/", "clip/clip_g/", "unet/add_embed/"})])
def test_lowered_program_carries_block_scopes(cfg, inner):
    """Every spatial transformer runs under ``xfmr`` inside ``unet``;
    SDXL's second encoder under ``clip_g`` inside ``clip``, its added
    embedding under ``add_embed`` inside ``unet``."""
    params = jax.eval_shape(lambda: init_pipeline(jax.random.PRNGKey(0),
                                                  cfg))
    b, tl, hw = 2, cfg.text_len, cfg.latent_hw
    plan = samplers_mod.get_sampler("euler").plan(
        sched_mod.NoiseSchedule(), 1, 1)
    spec = jax.ShapeDtypeStruct
    text = jax.jit(build_denoise(cfg, "euler", False)).lower(
        params, spec((b, tl), jnp.int32), spec((b, tl), jnp.int32),
        spec((b,), jnp.float32), spec((b, hw, hw, 4), jnp.float32),
        plan).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*/[^"]*)"', text))
    for part in inner:
        assert any(part in p for p in paths), part


def test_span_records_nothing_without_a_session():
    assert not TraceAnnotation.is_enabled()
    s = span("engine.launch", None, phase="fused", rids=(1, 2), rows=2)
    with s:
        pass
    assert s._ann is None and s._tele is None


def test_span_feeds_phase_telemetry():
    """With a Telemetry and a phase the span is one ``phase_seconds``
    observation and one trace span, on the given clock."""
    now = [1.0]
    tele = Telemetry(tracer=TraceRecorder())
    with span("engine.launch", tele, phase="fused", engine="diffusion",
              clock=lambda: now[0], rids=(4, 5), rows=2, cfg=True):
        now[0] = 1.25
    h = tele.registry.get("phase_seconds")
    assert h.count(engine="diffusion", phase="fused") == 1
    assert h.sum(engine="diffusion", phase="fused") == pytest.approx(0.25)
    (agg,) = [s for s in tele.tracer.spans if s.rid is None]
    assert agg.name == "fused" and agg.args["rids"] == [4, 5]
    assert agg.args["rows"] == 2 and agg.args["cfg"] is True
    assert {s.rid for s in tele.tracer.spans} == {None, 4, 5}


def test_engine_spans_on_the_profiler_clock(sd_params, tmp_path):
    """A traced ``step()`` writes ``engine.step`` holding
    ``engine.admit``/``pack``/``launch``/``retire`` with their args;
    the steps taken before the session record nothing."""
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=2)
    for rid in (0, 1):
        eng.submit(_req(rid, guidance_scale=7.0))
    eng.step()                                   # compiles, untraced
    jax.block_until_ready([r.image for r in eng.finished])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for rid in (10, 11):
            eng.submit(_req(rid, guidance_scale=7.0))
        eng.step()
        jax.block_until_ready([r.image for r in eng.finished])
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    names = [s[0] for s in spans]
    assert names == ["engine.submit", "engine.submit", "engine.step",
                     "engine.admit", "engine.pack", "engine.launch",
                     "engine.retire"]
    assert [s[3]["rid"] for s in spans[:2]] == [10, 11]
    step = spans[2]
    for child in spans[3:]:
        assert step[1] <= child[1] and child[2] <= step[2]
    for a, b in zip(spans[3:], spans[4:]):
        assert a[2] <= b[1]                       # children in order
    launch = spans[5][3]
    assert launch == {"rids": "10 11", "rows": 2, "bucket": 4, "steps": 3,
                      "sampler": "euler", "cfg": 1, "latent_hw": 8,
                      "vae_chunk": 2}
    assert spans[4][3] == {"rows": 2} and spans[6][3] == {"rows": 2}


def test_phase_telemetry_never_waits_for_the_device(sd_params,
                                                    monkeypatch):
    """Telemetry on, no cost model: ``phase_seconds`` is host time and
    the engine never blocks on the device."""
    eng = DiffusionEngine(sd_params, TINY_SD, max_batch=1,
                          metrics=Telemetry())
    eng.submit(_req(0))
    eng.step()                                   # compile outside the check

    def refuse(*a, **k):
        raise AssertionError("the telemetry path waited for the device")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    eng.submit(_req(1))
    eng.submit(_req(2, preview_every=1))
    eng.run()
    h = eng.metrics.registry.get("phase_seconds")
    assert h.count(engine="diffusion", phase="fused") == 2
    assert h.count(engine="diffusion", phase="clip") == 1
    assert h.count(engine="diffusion", phase="unet_step") == 3
    assert h.count(engine="diffusion", phase="vae") == 1
