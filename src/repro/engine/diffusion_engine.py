"""Request-based text-to-image engine (CLIP -> UNet scan -> VAE).

This is the diffusion half of the unified :class:`repro.engine.Engine`
surface.  Design points:

* **One jitted program per (sampler, steps-bucket, shape, cfg?, batch)**
  — ``build_denoise`` emits a pure function whose multi-step denoise
  loop is a single ``lax.scan`` over the sampler's step plan, so an
  N-step generation costs one trace, not N.  ``DiffusionEngine`` keeps
  an explicit compile cache keyed on the bucketed request shape and
  counts traces (``engine.traces``) so tests can assert no retrace.
* **Continuous micro-batching** — concurrent requests are grouped by
  compile key, packed into a fixed batch bucket (padded rows replicate
  row 0 and are discarded), run as one program, and retired.  Mirrors
  the slot mechanics of ``serving.scheduler.ContinuousBatcher``.
* **Per-request state rides in batched arrays** — seeds become
  per-request initial noise rows, guidance scales a ``(B,)`` vector,
  so a request's pixels depend only on its own row and co-batching is
  bit-transparent.
* **Classifier-free guidance** — requests with a negative prompt or a
  non-unit ``guidance_scale`` run the UNet on cond + uncond contexts;
  plain requests compile a single-branch program (the two variants are
  separate compile-cache entries).
* **Streaming lifecycle** — ``submit()`` returns a
  :class:`repro.engine.events.RequestHandle`; the engine emits typed
  events (``Admitted``/``Progress``/``PreviewLatent``/``Finished``/
  ``Cancelled``) on its :class:`~repro.engine.events.EventBus`.
  Requests with ``preview_every > 0`` run on a *segmented* program set
  (one jitted CLIP encode + one jitted single-solver-step program
  applied ``steps`` times + one jitted finalize/VAE-decode) so the
  host sees an x0-space ``PreviewLatent`` every N steps and can
  ``cancel()`` between steps; plain requests keep the original fused
  single-``lax.scan`` program, so existing ``run()`` callers stay
  bit-identical.  Both program sets live in the same explicit compile
  cache (segment programs need no steps bucket: a 1-step program
  serves every step count).
* **SLO-aware admission** — queued requests are popped
  earliest-deadline-first (``deadline_ms``, ties broken by
  ``priority`` then arrival); with no deadlines this reduces exactly
  to the old FIFO order.
* **Feasibility admission control (opt-in)** — with a
  :class:`repro.engine.costmodel.CostModel` attached
  (``cost_model=...``), ``submit()`` rejects a request whose
  estimated phase-composed service time (CLIP + steps x UNet + VAE,
  or the observed fused-program cost) exceeds its ``deadline_ms``
  budget — terminal :class:`~repro.engine.events.Rejected`, nothing
  enqueued — and each ``step()`` sweeps queued requests whose
  deadline expired or became infeasible while they waited.  The
  engine feeds the model online: every quantum's duration (measured
  on the event clock, first-trace observations skipped) refines the
  per-phase EWMA.  With ``cost_model=None`` (the default) every code
  path is bit-identical to the model-free engine.

Model-file quantization (``quantize_pipeline``) and the role-tagged
offload accounting are unchanged from the paper's study — the engine
only reorganizes the host-side request plumbing and the jit boundary.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.policy import OffloadPolicy, get_policy
from repro.core.qlinear import quantize_params
from repro.diffusion import schedule as sched_mod
from repro.engine import events as ev
from repro.engine import samplers as samplers_mod
from repro.engine.api import GenerateRequest, GenerateResult, uses_cfg
from repro.engine.config import EngineConfig, UNSET, resolve
from repro.models import clip as clip_mod
from repro.models import unet as unet_mod
from repro.models import vae as vae_mod
from repro.obs import span


@dataclasses.dataclass(frozen=True)
class SDConfig:
    name: str = "sd-turbo"
    unet: unet_mod.UNetConfig = unet_mod.SD15_UNET
    vae: vae_mod.VAEConfig = vae_mod.SD15_VAE
    clip: Any = None   # ModelConfig; None -> clip_mod.clip_config()
    latent_hw: int = 64          # 512x512 image -> 64x64 latent
    text_len: int = 77
    steps: int = 1               # SD-Turbo single step
    # Second text encoder (SDXL's OpenCLIP ViT-bigG), on the same
    # tokens: its states join the context after the first encoder's,
    # and its pooled state feeds the UNet's added embedding.
    clip_g: Any = None
    # Both encoders' output layer, stable-diffusion.cpp's --clip-skip:
    # 1 the last layer with the final layer norm, 2 the penultimate
    # layer without it (SDXL).
    clip_skip: int = 1

    def clip_cfg(self):
        return self.clip or clip_mod.clip_config()


SD_TURBO = SDConfig()
TINY_SD = SDConfig(name="tiny-sd", unet=unet_mod.TINY_UNET,
                   vae=vae_mod.TINY_VAE, clip=clip_mod.TINY_CLIP,
                   latent_hw=8, steps=1)
# SDXL base 1.0 at 1024x1024: CLIP ViT-L/14 and OpenCLIP ViT-bigG/14.
SDXL = SDConfig(name="sdxl", unet=unet_mod.SDXL_UNET,
                vae=vae_mod.SDXL_VAE, clip=clip_mod.clip_config(),
                clip_g=clip_mod.clip_config(d_model=1280, layers=32,
                                            heads=20),
                latent_hw=128, clip_skip=2)
TINY_XL = SDConfig(name="tiny-xl", unet=unet_mod.TINY_XL_UNET,
                   vae=dataclasses.replace(vae_mod.TINY_VAE,
                                           scale_factor=0.13025),
                   clip=clip_mod.clip_config(d_model=16, layers=3, heads=2,
                                             vocab=512),
                   clip_g=clip_mod.clip_config(d_model=32, layers=3,
                                               heads=2, vocab=512),
                   latent_hw=8, clip_skip=2)


def init_pipeline(key: jax.Array, cfg: SDConfig) -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "clip": clip_mod.init_clip(ks[0], cfg.clip_cfg()),
        "unet": unet_mod.init_unet(ks[1], cfg.unet),
        "vae": vae_mod.init_vae_decoder(ks[2], cfg.vae),
    }
    if cfg.clip_g is not None:
        p["clip_g"] = clip_mod.init_clip(jax.random.fold_in(key, 3),
                                         cfg.clip_g, projection=True)
    return p


def quantize_pipeline(params: dict, policy: OffloadPolicy) -> dict:
    """GGML-style model-file quantization (the paper's two models)."""
    return quantize_params(params, policy)


def steps_bucket(steps: int) -> int:
    """Round a step count up to the next power of two.

    All step counts in one bucket share a compiled scan (padding steps
    are masked no-ops in the sampler plan), bounding compile count at
    log2(max_steps) per (sampler, shape).  The trade-off is explicit:
    padded steps still run the UNet (a scan cannot skip iterations),
    so a steps=5 request pays 8 evals — bucketing buys bounded
    compiles (~10s each on CPU) at the cost of up to ~2x steady-state
    denoise work for off-bucket step counts.
    """
    b = 1
    while b < steps:
        b *= 2
    return b


def _encode_one(params, cfg: SDConfig, tokens):
    """The UNet's text conditioning of one batch of prompts: the context
    ``(B, S, ctx_dim)``, and with a second encoder its pooled state."""
    ctx = clip_mod.clip_encode(params["clip"], cfg.clip_cfg(), tokens,
                               cfg.clip_skip)
    if cfg.clip_g is None:
        return ctx, None
    with jax.named_scope("clip_g"):
        ctx_g, pooled = clip_mod.clip_encode(params["clip_g"], cfg.clip_g,
                                             tokens, cfg.clip_skip,
                                             pooled=True)
    return jnp.concatenate([ctx, ctx_g], -1), pooled


def _encode(params, cfg: SDConfig, tokens, neg_tokens, use_cfg: bool):
    """The conditioning of the prompts (and of the negative prompts
    under CFG), under the ``clip`` scope."""
    with jax.named_scope("clip"):
        cond = _encode_one(params, cfg, tokens)
        cond_u = (_encode_one(params, cfg, neg_tokens) if use_cfg
                  else None)
    return cond, cond_u


def _eps(params, cfg: SDConfig, xm, tb, cond, cond_u, g):
    """The noise prediction of one solver step: one UNet evaluation per
    guidance branch, each under the ``unet`` scope."""
    b, hw = xm.shape[0], xm.shape[1]
    side = hw * 2 ** (len(cfg.vae.channel_mult) - 1)

    def unet(c):
        ctx, pooled = c
        added = {} if pooled is None else dict(
            text_embeds=pooled,
            time_ids=unet_mod.image_size_ids(b, side))
        with jax.named_scope("unet"):
            return unet_mod.apply_unet(params["unet"], cfg.unet,
                                       xm.astype(jnp.bfloat16), tb,
                                       ctx, **added).astype(jnp.float32)

    eps = unet(cond)
    if cond_u is not None:
        eps_u = unet(cond_u)
        eps = eps_u + g * (eps - eps_u)
    return eps


def _decode(params, cfg: SDConfig, x0):
    with jax.named_scope("vae"):
        return vae_mod.decode(params["vae"], cfg.vae,
                              x0.astype(jnp.bfloat16))


def build_denoise(cfg: SDConfig, sampler_name: str, use_cfg: bool, *,
                  decode: bool = True) -> Callable:
    """Build the pure denoise program for one sampler / guidance mode.

    Returns ``fn(params, tokens, neg_tokens, gscale, noise, plan)``
    mapping ``(B, text_len)`` prompts and ``(B, hw, hw, 4)`` unit noise
    to images (or x0 latents with ``decode=False``).  Fully traceable —
    the engine jits it; ``pipeline.generate`` and ``jax.eval_shape``
    callers use it directly.  CLIP, every UNet evaluation and the VAE
    run under the ``clip``/``unet``/``vae`` named scopes, so a device
    trace attributes each op to its model.
    """
    sampler = samplers_mod.get_sampler(sampler_name)
    sched = sched_mod.NoiseSchedule()

    def fn(params, tokens, neg_tokens, gscale, noise, plan):
        b = tokens.shape[0]
        ctx, ctx_u = _encode(params, cfg, tokens, neg_tokens, use_cfg)
        x = sampler.init_latent(noise.astype(jnp.float32), plan)
        g = gscale[:, None, None, None]

        def body(x, step):
            xm, t = sampler.model_input(x, step)
            tb = jnp.broadcast_to(t, (b,)).astype(jnp.int32)
            eps = _eps(params, cfg, xm, tb, ctx, ctx_u, g)
            x_new = sampler.update(sched, x, eps, step)
            return jnp.where(step["valid"], x_new, x), None

        x, _ = jax.lax.scan(body, x, plan)
        x0 = sampler.finalize(x)
        if not decode:
            return x0
        return _decode(params, cfg, x0)
    return fn


def build_encode(cfg: SDConfig, use_cfg: bool) -> Callable:
    """Prompt-encoding half of the segmented (preview-streaming) path:
    ``fn(params, tokens, neg_tokens) -> (cond, cond_uncond|None)``, each
    ``(context, pooled state|None)``."""

    def fn(params, tokens, neg_tokens):
        return _encode(params, cfg, tokens, neg_tokens, use_cfg)
    return fn


def build_denoise_step(cfg: SDConfig, sampler_name: str,
                       use_cfg: bool) -> Callable:
    """One solver step of the segmented path — the same math as the
    ``lax.scan`` body in :func:`build_denoise`, exposed as its own
    program so the host can observe/cancel between steps:
    ``fn(params, ctx, ctx_u, gscale, x, step) -> x`` where ``step`` is
    one per-step slice of the sampler plan (scalars)."""
    sampler = samplers_mod.get_sampler(sampler_name)
    sched = sched_mod.NoiseSchedule()

    def fn(params, ctx, ctx_u, gscale, x, step):
        b = x.shape[0]
        g = gscale[:, None, None, None]
        xm, t = sampler.model_input(x, step)
        tb = jnp.broadcast_to(t, (b,)).astype(jnp.int32)
        eps = _eps(params, cfg, xm, tb, ctx, ctx_u if use_cfg else None, g)
        x_new = sampler.update(sched, x, eps, step)
        return jnp.where(step["valid"], x_new, x)
    return fn


def build_finalize_decode(cfg: SDConfig, sampler_name: str) -> Callable:
    """Tail of the segmented path: ``fn(params, x) -> images`` applies
    the sampler's finalize then the VAE decoder."""
    sampler = samplers_mod.get_sampler(sampler_name)

    def fn(params, x):
        return _decode(params, cfg, sampler.finalize(x))
    return fn


def request_noise(req: GenerateRequest, hw: int) -> jax.Array:
    """Initial unit-normal latent for one request, from its seed only."""
    return jax.random.normal(jax.random.PRNGKey(req.seed), (hw, hw, 4),
                             jnp.float32)


class DiffusionEngine(ev.EventStreamMixin):
    """Micro-batching diffusion engine (implements the Engine protocol).

    ``step()`` pops up to ``max_batch`` queued requests that share a
    compile group — same (sampler, steps, latent size, guidance mode,
    preview cadence) — seeded earliest-deadline-first, pads them to
    the batch bucket, and either runs the jitted scan program from the
    compile cache and retires the batch (no previews: the original
    fused path, bit-identical results) or advances the segmented
    per-step program by one denoise step, emitting
    ``Progress``/``PreviewLatent`` events and honoring ``cancel()``
    between steps.  ``run()`` drains the queue.  ``engine.traces``
    counts actual jit traces across all program kinds.
    """

    def __init__(self, params: dict, cfg: SDConfig, *,
                 config: EngineConfig | None = None,
                 max_batch: int = UNSET,
                 bus: ev.EventBus | None = UNSET,
                 clock: Callable[[], float] = UNSET,
                 cost_model=UNSET, metrics=UNSET,
                 weight_quant: str | None = UNSET):
        # Config-first construction (PR 10): loose kwargs are a
        # deprecation shim resolved onto config.diffusion — explicit
        # kwargs win, gated bit-identical in tests.
        self.config, diffc = resolve(config, "diffusion", dict(
            max_batch=max_batch, bus=bus, clock=clock,
            cost_model=cost_model, metrics=metrics,
            weight_quant=weight_quant))
        max_batch = diffc.max_batch
        weight_quant = self.config.weight_quant
        bus, clock = self.config.bus, self.config.clock
        cost_model, metrics = (self.config.cost_model,
                               self.config.metrics)
        if weight_quant is not None:
            # Opt-in quantized weights (GGML model-file semantics):
            # CLIP/UNet/VAE linears move to blocked storage and route
            # through core.qlinear onto the quantized matmul kernels.
            params = quantize_pipeline(params, get_policy(weight_quant))
        self.weight_quant = weight_quant
        self.params = params
        self.cfg = cfg
        self.max_batch = max_batch
        self.queue: deque[GenerateRequest] = deque()
        self.finished: list[GenerateResult] = []
        self.traces = 0
        self._fns: dict[tuple, Callable] = {}   # explicit compile cache
        self.bus = bus if bus is not None else ev.EventBus(clock)
        self._inflight: dict | None = None      # segmented batch state
        self._meta: dict[int, tuple] = {}       # rid -> (seq, deadline, prio)
        self._subseq = 0
        self.cost_model = cost_model            # None -> no admission ctrl
        self.rejections = 0
        self.metrics = metrics                  # None -> no instrumentation
        self.quanta = 0                         # non-idle step() count

    # ------------------------------------------------------------ API
    def submit(self, request: GenerateRequest) -> ev.RequestHandle:
        with span("engine.submit", rid=request.rid):
            return self._submit(request)

    def _submit(self, request: GenerateRequest) -> ev.RequestHandle:
        samplers_mod.get_sampler(request.sampler)   # fail fast on typos
        if request.steps < 1:
            raise ValueError(f"steps must be >= 1, got {request.steps}")
        if request.preview_every < 0:
            raise ValueError(
                f"preview_every must be >= 0, got {request.preview_every}")
        hw = (self.cfg.latent_hw if request.latent_hw is None
              else request.latent_hw)    # 0 is invalid, not "default"
        down = 2 ** (len(self.cfg.unet.channel_mult) - 1)
        if hw < down or hw % down:
            raise ValueError(
                f"latent_hw={hw} must be a positive multiple of the "
                f"UNet downsample factor {down}")
        if request.rid in self._meta \
                or self.bus.terminal(request.rid) is not None:
            raise ValueError(f"duplicate rid {request.rid}")
        if self.metrics is not None:
            # Before admission control: rejected-at-submit requests are
            # telemetry-visible too (submission is not a bus event).
            self.metrics.request_submitted(request.rid, "diffusion",
                                           self.bus.clock())
        if self.cost_model is not None and request.deadline_ms is not None:
            est = self.cost_model.estimate_diffusion(self, request)
            if est is not None:
                # Queueing-delay-aware admission: charge the expected
                # wait behind already-queued work, so a feasible-in-
                # isolation request behind a deep queue is rejected up
                # front instead of expiring in the sweep later.
                est += self.cost_model.queue_wait(self)
            budget = request.deadline_ms / 1e3
            if est is not None and est > budget:
                self.rejections += 1
                self.bus.emit(ev.Rejected, request.rid, estimated_s=est,
                              budget_s=budget, reason="infeasible")
                return self.handle(request.rid)
        deadline = (float("inf") if request.deadline_ms is None
                    else self.bus.clock() + request.deadline_ms / 1e3)
        request._deadline = deadline
        self._meta[request.rid] = (self._subseq, deadline, request.priority)
        self._subseq += 1
        self.queue.append(request)
        self._obs_sched()
        return self.handle(request.rid)

    # ------------------------------------------- fleet migration hooks
    def evacuate(self, reason: str = "evacuate") -> list[GenerateRequest]:
        """Drain hook for fleet migration: return every live request —
        in-flight segmented ones first (``Preempted`` emitted, their
        partial denoise is abandoned), then the queue in arrival order —
        with no terminal events, so a surviving replica can ``adopt()``
        them.  Restarting from the original seed is bit-exact: the seed
        alone determines the initial latent and the solver is
        deterministic, so a rerun matches an uninterrupted run."""
        out: list[GenerateRequest] = []
        st = self._inflight
        if st is not None:
            for r in st["reqs"]:
                if r.rid not in st["cancelled"]:
                    self.bus.emit(ev.Preempted, r.rid, reason=reason)
                    out.append(r)
            self._inflight = None
        out.extend(self.queue)
        self.queue = deque()
        for r in out:
            self._meta.pop(r.rid, None)
        return out

    def adopt(self, request: GenerateRequest) -> ev.RequestHandle:
        """Admit a request evacuated from another engine on the same
        shared bus.  Unlike ``submit()`` this skips the duplicate-rid
        guard (the rid's prior admission legitimately lives on the bus)
        and submit-time feasibility rejection (the request was already
        admitted once; the per-step queue sweep still applies), and it
        keeps the request's original absolute deadline
        (``request._deadline``) instead of restarting the budget.  At
        batch pop an already-admitted rid re-enters via
        ``Progress(phase="resume")``, never a second ``Admitted``."""
        self._meta[request.rid] = (self._subseq, request._deadline,
                                   request.priority)
        self._subseq += 1
        self.queue.append(request)
        return self.handle(request.rid)

    def has_work(self) -> bool:
        return bool(self.queue) or self._inflight is not None

    def next_deadline(self) -> float:
        """Earliest SLO deadline over queued + in-flight requests
        (+inf if none declare one) — the router's multiplex key."""
        cands = [self._meta[r.rid][1] for r in self.queue]
        if self._inflight is not None:
            cands += [self._meta[r.rid][1] for r in self._inflight["reqs"]
                      if r.rid not in self._inflight["cancelled"]]
        return min(cands, default=float("inf"))

    def next_slack(self) -> float:
        """Minimum estimated *slack* — deadline minus now minus the
        estimated (remaining) service time — over queued + in-flight
        requests; +inf when none declares a deadline.  The router's
        multiplex key when cost models are attached; requests the
        model cannot price yet fall back to raw deadline ordering
        (estimate 0)."""
        cm = self.cost_model
        now = self.bus.clock()
        best = float("inf")
        for r in self.queue:
            dl = self._meta[r.rid][1]
            if dl == float("inf"):
                continue
            est = cm.estimate_diffusion(self, r) if cm else None
            best = min(best, dl - now - (est or 0.0))
        st = self._inflight
        if st is not None:
            for r in st["reqs"]:
                if r.rid in st["cancelled"]:
                    continue
                dl = self._meta[r.rid][1]
                if dl == float("inf"):
                    continue
                est = (cm.remaining_diffusion(self, r, st["i"])
                       if cm else None)
                best = min(best, dl - now - (est or 0.0))
        return best

    def cancel(self, rid: int) -> bool:
        """Abort a request: queued requests leave the queue; requests
        inside a segmented batch stop emitting and are dropped at the
        batch's end (their rows keep computing — co-batched rows cannot
        shrink a compiled shape).  Requests already in a *fused-scan*
        batch retire atomically and cannot be cancelled mid-program.
        """
        for r in self.queue:
            if r.rid == rid:
                self.queue.remove(r)
                self.bus.emit(ev.Cancelled, rid)
                return True
        st = self._inflight
        if st is not None:
            for r in st["reqs"]:
                if r.rid == rid and rid not in st["cancelled"]:
                    st["cancelled"].add(rid)
                    self.bus.emit(ev.Cancelled, rid)
                    return True
        return False

    def step(self) -> int:
        """One scheduling quantum: advance the in-flight segmented
        batch by one denoise step, or pop + run a new micro-batch;
        returns #requests progressed (0 if idle).

        Host work is named on the profiler's clock (``repro.obs.span``):
        ``engine.step`` holds ``engine.admit`` (pop and events),
        ``engine.pack`` (request rows to device arrays),
        ``engine.launch`` (each jitted call, with the batch's ``rids``,
        ``rows``, ``bucket``, ``steps``, ``sampler``, ``cfg``,
        ``latent_hw`` and ``vae_chunk``, the images per VAE decode
        call) and
        ``engine.retire`` (row slices and ``Finished``)."""
        with span("engine.step"):
            return self._step()

    def _step(self) -> int:
        if self.cost_model is not None and self.queue:
            self._sweep_infeasible()
        if self._inflight is not None:
            self.quanta += 1
            self._obs_sched()
            return self._segment_quantum()
        if not self.queue:
            return 0
        self.quanta += 1
        self._obs_sched()
        with span("engine.admit"):
            batch, gkey = self._admit()
        if gkey[4]:                      # preview_every > 0: segmented
            self._start_segmented(batch, gkey)
            return self._segment_quantum()
        self._run_batch(batch, gkey)
        return len(batch)

    def _admit(self) -> tuple[list[GenerateRequest], tuple]:
        """Pop the next micro-batch: the EDF seed and up to
        ``max_batch - 1`` queued requests of its compile group."""
        seed = min(self.queue, key=self._edf_key)
        gkey = self._group_key(seed)
        batch: list[GenerateRequest] = [seed]
        rest: deque[GenerateRequest] = deque()
        for r in self.queue:
            if r is seed:
                continue
            if len(batch) < self.max_batch and self._group_key(r) == gkey:
                batch.append(r)
            else:
                rest.append(r)
        self.queue = rest
        for i, r in enumerate(batch):
            if self.bus.admitted(r.rid):   # adopted after a migration
                self.bus.emit(ev.Progress, r.rid, phase="resume",
                              step=0, total=gkey[1])
            else:
                self.bus.emit(ev.Admitted, r.rid, slot=i)
        return batch, gkey

    def run(self, max_steps: int = 10_000) -> list[GenerateResult]:
        for _ in range(max_steps):
            if not self.has_work():
                break
            self.step()
        return list(self.finished)    # snapshot: later runs keep appending

    # ------------------------------------------------------ internals
    def _use_cfg(self, req: GenerateRequest) -> bool:
        return uses_cfg(req.neg_tokens, req.guidance_scale)

    def _edf_key(self, req: GenerateRequest) -> tuple:
        """Same policy as the LM scheduler: expired deadlines sort
        behind every still-feasible request, then EDF, then priority,
        then arrival (no deadlines -> exact FIFO)."""
        seq, deadline, prio = self._meta[req.rid]
        expired = deadline < self.bus.clock()
        return (expired, deadline, -prio, seq)

    def _sweep_infeasible(self) -> None:
        """Cost-model housekeeping, once per ``step()``: queued
        requests whose deadline already expired — or can provably no
        longer be met (now + estimated service > deadline) — go
        straight to terminal ``Rejected`` instead of sorting behind
        feasible work forever (the queue stays bounded by live,
        winnable requests)."""
        now = self.bus.clock()
        keep: deque[GenerateRequest] = deque()
        for r in self.queue:
            dl = self._meta[r.rid][1]
            if dl == float("inf"):
                keep.append(r)
                continue
            expired = dl < now
            est = self.cost_model.estimate_diffusion(self, r)
            if expired or (est is not None and now + est > dl):
                self.rejections += 1
                self.bus.emit(ev.Rejected, r.rid, estimated_s=est or 0.0,
                              budget_s=dl - now,
                              reason="expired" if expired
                              else "infeasible")
            else:
                keep.append(r)
        self.queue = keep

    def _observe(self, key: tuple, t0: float, traces0: int, out) -> None:
        """Feed one measured program duration into the cost model.
        Skips quanta that paid a jit trace (compile time would poison
        the steady-state EWMA) and blocks on the output so async
        dispatch cannot under-report device time."""
        if self.cost_model is None or self.traces != traces0:
            return
        jax.block_until_ready(out)
        self.cost_model.observe(key, self.bus.clock() - t0)

    def _launch(self, phase: str, rids: list, **args) -> span:
        """The span of one jitted call: on the profiler's clock, and
        under a ``Telemetry`` one ``phase_seconds`` observation and
        trace span of the call's host time.  Unlike the cost-model
        ``_observe`` it never skips first-trace quanta (phase counts
        reconcile exactly with emitted events, so a first observation
        includes compile time) and never waits for the device."""
        return span("engine.launch", self.metrics, phase=phase,
                    engine="diffusion", clock=self.bus.clock, rids=rids,
                    weight_quant=self.weight_quant, **args)

    def _vae_chunk(self, hw: int) -> int:
        """Images per VAE decode call of a batch at latent size ``hw``."""
        return vae_mod.images_per_call(self.cfg.vae, self.max_batch, hw)

    def _obs_sched(self) -> None:
        if self.metrics is None:
            return
        self.metrics.gauge(
            "engine_queue_depth", "queued requests by engine",
            labels=("engine",)).set(len(self.queue), engine="diffusion")
        st = self._inflight
        live = 0 if st is None else sum(
            1 for r in st["reqs"] if r.rid not in st["cancelled"])
        self.metrics.gauge(
            "diffusion_inflight",
            "live requests in the segmented in-flight batch").set(live)
        self.metrics.gauge("diffusion_traces",
                           "cumulative jit traces").set(self.traces)

    def _group_key(self, req: GenerateRequest) -> tuple:
        fixed = samplers_mod.get_sampler(req.sampler).fixed_steps
        # preview_decode joins the key only when previews actually
        # stream (it is inert on the fused path), so plain requests
        # never split batches over it.
        return (req.sampler, fixed or req.steps,
                req.latent_hw or self.cfg.latent_hw, self._use_cfg(req),
                req.preview_every,
                bool(req.preview_every and req.preview_decode))

    def _counted_jit(self, key: tuple, inner: Callable) -> Callable:
        """Compile-cache lookup; wraps ``inner`` so ``self.traces``
        counts actual jit traces."""
        fn = self._fns.get(key)
        if fn is None:
            def counted(*args, _inner=inner):
                self.traces += 1        # runs at trace time only
                return _inner(*args)

            fn = jax.jit(counted)
            self._fns[key] = fn
        return fn

    def _compiled(self, sampler: str, sbucket: int, hw: int,
                  use_cfg: bool) -> Callable:
        return self._counted_jit(
            (sampler, sbucket, hw, use_cfg, self.max_batch),
            build_denoise(self.cfg, sampler, use_cfg))

    def _pack(self, reqs: list[GenerateRequest], hw: int) -> tuple:
        """Batch request rows, padding to the bucket with row 0
        (padded rows are replicas and are discarded at retire)."""
        tl = self.cfg.text_len

        def tok_arr(t):
            return jnp.asarray(t, jnp.int32).reshape(tl)

        toks = [tok_arr(r.tokens) for r in reqs]
        negs = [tok_arr(r.neg_tokens) if r.neg_tokens is not None
                else jnp.zeros((tl,), jnp.int32) for r in reqs]
        noises = [request_noise(r, hw) for r in reqs]
        scales = [float(r.guidance_scale) for r in reqs]
        while len(toks) < self.max_batch:    # pad-to-bucket with row 0
            toks.append(toks[0])
            negs.append(negs[0])
            noises.append(noises[0])
            scales.append(scales[0])
        return (jnp.stack(toks), jnp.stack(negs),
                jnp.asarray(scales, jnp.float32), jnp.stack(noises))

    # ------------------------------------------------- fused scan path
    def _run_batch(self, reqs: list[GenerateRequest], gkey: tuple) -> None:
        sampler_name, steps, hw, use_cfg = gkey[:4]
        sbucket = steps_bucket(steps)
        with span("engine.pack", rows=len(reqs)):
            toks, negs, scales, noises = self._pack(reqs, hw)
            sampler = samplers_mod.get_sampler(sampler_name)
            plan = sampler.plan(sched_mod.NoiseSchedule(), steps, sbucket)
            fn = self._compiled(sampler_name, sbucket, hw, use_cfg)
        t0, tr0 = self.bus.clock(), self.traces
        with self._launch("fused", [r.rid for r in reqs], rows=len(reqs),
                          bucket=sbucket, steps=steps, sampler=sampler_name,
                          cfg=use_cfg, latent_hw=hw,
                          vae_chunk=self._vae_chunk(hw)):
            imgs = fn(self.params, toks, negs, scales, noises, plan)
        self._observe(("diff", self.cfg.name, "fused", sampler_name,
                       sbucket, hw, use_cfg, self.max_batch,
                       self.weight_quant), t0, tr0, imgs)
        with span("engine.retire", rows=len(reqs)):
            for i, r in enumerate(reqs):
                res = GenerateResult(
                    rid=r.rid, image=imgs[i], sampler=sampler_name,
                    steps=steps, seed=r.seed, decode_steps=steps)
                self.finished.append(res)
                self.bus.emit(ev.Finished, r.rid, result=res)

    # ------------------------------------------------- segmented path
    def _start_segmented(self, reqs: list[GenerateRequest],
                         gkey: tuple) -> None:
        sampler_name, steps, hw, use_cfg = gkey[:4]
        sampler = samplers_mod.get_sampler(sampler_name)
        with span("engine.pack", rows=len(reqs)):
            toks, negs, scales, noises = self._pack(reqs, hw)
            enc = self._counted_jit(("enc", use_cfg, self.max_batch),
                                    build_encode(self.cfg, use_cfg))
            # Unpadded plan: the 1-step segment program serves any step
            # count, so segmented requests never pay pow2 padding steps.
            plan = sampler.plan(sched_mod.NoiseSchedule(), steps, steps)
        t0, tr0 = self.bus.clock(), self.traces
        with self._launch("clip", [r.rid for r in reqs], rows=len(reqs),
                          cfg=use_cfg):
            ctx, ctx_u = enc(self.params, toks, negs)
        self._observe(("diff", self.cfg.name, "clip", use_cfg,
                       self.max_batch, self.weight_quant), t0, tr0, ctx)
        self._inflight = dict(
            reqs=reqs, key=(sampler_name, steps, hw, use_cfg),
            x=sampler.init_latent(noises, plan), ctx=ctx, ctx_u=ctx_u,
            g=scales, plan=plan, i=0, cancelled=set())

    def _segment_quantum(self) -> int:
        st = self._inflight
        sampler_name, steps, hw, use_cfg = st["key"]
        live = [(row, r) for row, r in enumerate(st["reqs"])
                if r.rid not in st["cancelled"]]
        if not live:                     # everyone cancelled mid-flight
            self._inflight = None
            return 0
        i = st["i"]
        step_slice = {k: v[i] for k, v in st["plan"].items()}
        fn = self._counted_jit(
            ("seg", sampler_name, hw, use_cfg, self.max_batch),
            build_denoise_step(self.cfg, sampler_name, use_cfg))
        t0, tr0 = self.bus.clock(), self.traces
        with self._launch("unet_step", [r.rid for _row, r in live],
                          rows=len(live), bucket=steps, steps=steps,
                          sampler=sampler_name, cfg=use_cfg, step=i + 1):
            st["x"] = fn(self.params, st["ctx"], st["ctx_u"], st["g"],
                         st["x"], step_slice)
        self._observe(("diff", self.cfg.name, "unet_step", sampler_name,
                       hw, use_cfg, self.max_batch, self.weight_quant),
                      t0, tr0, st["x"])
        st["i"] = i + 1
        sampler = samplers_mod.get_sampler(sampler_name)
        at_stride = [(row, r) for row, r in live
                     if st["i"] % r.preview_every == 0 or st["i"] == steps]
        pv_imgs = None
        if any(r.preview_decode for _row, r in at_stride):
            # Pixel-space previews: run the (cached) finalize+VAE
            # program on the current latent.  Same compiled program as
            # the final decode — co-batched rows share one launch, and
            # preview_decode is in the group key so every row opted in.
            dec = self._counted_jit(("dec", sampler_name, hw,
                                     self.max_batch),
                                    build_finalize_decode(self.cfg,
                                                          sampler_name))
            t0, tr0 = self.bus.clock(), self.traces
            with self._launch("vae", [r.rid for _row, r in at_stride],
                              rows=len(at_stride), preview=True,
                              latent_hw=hw, vae_chunk=self._vae_chunk(hw)):
                pv_imgs = dec(self.params, st["x"])
            self._observe(("diff", self.cfg.name, "vae", hw,
                           self.max_batch, self.weight_quant), t0, tr0,
                          pv_imgs)
        for row, r in live:
            self.bus.emit(ev.Progress, r.rid, step=st["i"], total=steps,
                          phase="denoise")
        for row, r in at_stride:
            if r.preview_decode and pv_imgs is not None:
                self.bus.emit(ev.PreviewLatent, r.rid, step=st["i"],
                              total=steps, latent=pv_imgs[row],
                              decoded=True)
            else:
                self.bus.emit(ev.PreviewLatent, r.rid, step=st["i"],
                              total=steps,
                              latent=sampler.finalize(st["x"][row]))
        if st["i"] >= steps:
            dec = self._counted_jit(("dec", sampler_name, hw,
                                     self.max_batch),
                                    build_finalize_decode(self.cfg,
                                                          sampler_name))
            t0, tr0 = self.bus.clock(), self.traces
            with self._launch("vae", [r.rid for _row, r in live],
                              rows=len(live), latent_hw=hw,
                              vae_chunk=self._vae_chunk(hw)):
                imgs = dec(self.params, st["x"])
            self._observe(("diff", self.cfg.name, "vae", hw,
                           self.max_batch, self.weight_quant), t0, tr0,
                          imgs)
            with span("engine.retire", rows=len(live)):
                for row, r in live:
                    res = GenerateResult(
                        rid=r.rid, image=imgs[row], sampler=sampler_name,
                        steps=steps, seed=r.seed, decode_steps=steps)
                    self.finished.append(res)
                    self.bus.emit(ev.Finished, r.rid, result=res)
            self._inflight = None
        return len(live)
