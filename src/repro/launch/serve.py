"""Serving launcher: quantized-offload LM serving via the engine API.

  python -m repro.launch.serve --arch deepseek-moe-16b [--policy q8_0] \
      [--slots 4] [--requests 8] [--gen 16] [--deadline-ms 500] \
      [--admission] [--replicas 3] [--cost-model-path cm.json]

Requests flow through the ``ContinuousBatcher`` engine (the same
``submit()``/``stream()``/``run()`` protocol as the diffusion engine):
a fixed slot pool over the paged KV block pool, chunked-prefill
admission mid-flight, EOS/max-length retirement freeing blocks back to
the pool.  The host loop consumes the typed event stream —
``Admitted``/``TokenDelta``/``Finished``/``Rejected`` — so it reports
time-to-first-token per request instead of waiting for a
batch-and-drain ``run()``; ``--deadline-ms`` attaches an SLO budget to
every request and the scheduler admits earliest-deadline-first.
``--admission`` additionally attaches a phase-aware ``CostModel``
(seeded by a deadline-free calibration request, refined online by the
EWMA over observed quanta): requests whose estimated service time
exceeds their budget are **rejected up front** instead of expiring in
the queue, and the launcher reports the estimated-vs-budget detail per
rejection.  ``--cost-model-path`` persists that calibration as
versioned JSON — an existing file seeds the table (skipping the
calibration micro-run's trace-poisoned first impressions) and the
refined table is written back after the run.  ``--replicas N`` fronts
N data-parallel engine replicas with a ``FleetManager`` (shared event
bus, cost-balanced dispatch, watchdog-driven health) instead of one
engine — the rest of the host loop is unchanged, which is the point.
``--asr`` (with an encoder-decoder ``--arch`` such as
``whisper-large-v3``) serves streaming transcription through the
``AsrEngine`` instead: synthetic audio-frame embeddings are ingested
in encode quanta into the paged cross-attention pool, and the same
event loop reports transcripts, audio-prefix-cache hits, and
per-phase (encode/prefill/decode) quanta.
Runs reduced configs on CPU; on TPU the same path serves full configs
with TP-only weight sharding (no FSDP — see DESIGN.md) and the Pallas
fused-dequant kernels.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.configs import get_config, reduced as reduce_cfg, smoke_inputs
from repro.core.policy import get_policy
from repro.core.qlinear import param_bytes, quantize_params
from repro.engine import (AsrEngine, AsrEngineConfig, CostModel,
                          EngineConfig, Finished, FleetManager,
                          LMEngineConfig, Rejected, ReplicaSpec,
                          SpecDecodeConfig, TokenDelta,
                          TranscribeRequest, calibrate)
from repro.launch import compile_cache
from repro.models.frontend import synthetic_audio
from repro.models.transformer import init_lm
from repro.serving import ContinuousBatcher, Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--policy", default=None)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=None,
                    help="default: one per slot")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request SLO budget (EDF admission)")
    ap.add_argument("--asr", action="store_true",
                    help="serve streaming transcription through the "
                         "AsrEngine instead of LM decode (requires an "
                         "encoder-decoder --arch, e.g. "
                         "whisper-large-v3); audio embeddings are "
                         "synthetic frontend stubs, repeated across "
                         "slots so the audio prefix cache shows hits")
    ap.add_argument("--spec-draft", default=None, metavar="ARCH",
                    help="enable draft-model speculative decoding: the "
                         "named arch (reduced on CPU like --arch) "
                         "proposes tokens that the target verifies in "
                         "one fused paged-prefill launch per round; "
                         "needs a decoder-only --arch sharing the "
                         "target's vocabulary, incompatible with --asr")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per speculative round "
                         "(default 4)")
    ap.add_argument("--admission", action="store_true",
                    help="attach a phase-aware cost model: reject "
                         "requests whose estimated service time "
                         "exceeds their deadline budget up front")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a FleetManager fronting N "
                         "data-parallel engine replicas (default 1: "
                         "a single engine, no fleet layer)")
    ap.add_argument("--cost-model-path", default=None, metavar="PATH",
                    help="persist cost-model calibration as versioned "
                         "JSON: load it if the file exists, write the "
                         "refined table back after the run (implies a "
                         "cost model even without --admission)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="enable the telemetry layer and write the "
                         "final metrics snapshot (benchmarks/common.py "
                         "record schema) to PATH; PATH ending in "
                         "'.prom' writes Prometheus text exposition "
                         "instead")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable per-request span tracing and write a "
                         "Chrome trace-event JSON (Perfetto-loadable) "
                         "to PATH (implies the metrics layer)")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if jax.default_backend() == "cpu":
        cfg = reduce_cfg(cfg)
    policy = get_policy(args.policy or cfg.default_policy)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    qp = quantize_params(params, policy)
    print(f"{cfg.name} [{policy.name}]: {param_bytes(qp)/1e6:.1f} MB")

    if args.asr and not cfg.is_enc_dec:
        raise SystemExit(f"--asr needs an encoder-decoder arch; "
                         f"{cfg.name} is decoder-only")
    n_requests = args.requests or args.slots
    inp = smoke_inputs(jax.random.PRNGKey(1), cfg, batch=args.slots,
                       seq=args.prompt_len)
    if args.asr:
        max_len = AsrEngine.required_len(args.prompt_len, args.gen)
        audios = [synthetic_audio(jax.random.PRNGKey(100 + i), cfg)
                  for i in range(args.slots)]
    else:
        max_len = ContinuousBatcher.required_len(n_requests, args.slots,
                                                 args.prompt_len, args.gen)
    tele = None
    if args.metrics_out or args.trace_out:
        from repro.obs import Telemetry, TraceRecorder
        tele = Telemetry(tracer=TraceRecorder() if args.trace_out
                         else None)
    cm = None
    restored = False
    if args.admission or args.cost_model_path:
        if args.cost_model_path and os.path.exists(args.cost_model_path):
            cm = CostModel.load(args.cost_model_path)
            restored = True
            print(f"cost model restored from {args.cost_model_path} "
                  f"({len(cm.snapshot())} phase entries)")
        else:
            cm = CostModel()
        cm.metrics = tele   # estimate-vs-actual error histograms

    spec_decode = None
    if args.spec_draft:
        if args.asr:
            raise SystemExit("--spec-draft is decoder-only LM serving; "
                             "it cannot combine with --asr")
        dcfg = get_config(args.spec_draft)
        if jax.default_backend() == "cpu":
            dcfg = reduce_cfg(dcfg)
        if dcfg.vocab_size != cfg.vocab_size:
            raise SystemExit(
                f"--spec-draft {dcfg.name} vocab {dcfg.vocab_size} != "
                f"target vocab {cfg.vocab_size}")
        dparams = init_lm(jax.random.PRNGKey(2), dcfg)
        print(f"speculative draft {dcfg.name}: k={args.spec_k}")
        spec_decode = SpecDecodeConfig(draft_params=dparams,
                                       draft_cfg=dcfg, k=args.spec_k)

    # One EngineConfig describes every replica: shared knobs (cost
    # model, telemetry — any replica's observed quanta refine every
    # replica's estimates) at the top level, per-engine sections below.
    econf = EngineConfig(
        cost_model=cm, metrics=tele,
        lm=LMEngineConfig(slots=args.slots, max_len=max_len,
                          enc_embeds=(None if args.asr
                                      else inp.get("enc_embeds")),
                          spec_decode=spec_decode),
        asr=AsrEngineConfig(slots=args.slots, max_len=max_len))
    kind = "asr" if args.asr else "lm"

    def make_spec(name):
        return ReplicaSpec(name, params=qp, model_cfg=cfg, engine=kind,
                           config=econf)

    if args.replicas > 1:
        engine = FleetManager([make_spec(f"replica{i}")
                               for i in range(args.replicas)],
                              metrics=tele)
        batchers = [r.engine for r in engine.replicas]
    else:
        engine = make_spec("solo").make()
        batchers = [engine]
    if tele is not None:
        # Attach AFTER fleet/engine construction: the fleet rebinds
        # replica buses onto its shared one, and subscriptions live on
        # the bus object itself.
        tele.attach(engine.bus)
    prompts = np.asarray(inp["tokens"])

    def make_req(rid, i, deadline_ms=None):
        if args.asr:
            return TranscribeRequest(
                rid=rid, audio=audios[i % args.slots],
                prompt=prompts[i % args.slots].tolist(),
                max_new=args.gen, deadline_ms=deadline_ms)
        return Request(rid=rid, prompt=prompts[i % args.slots].tolist(),
                       max_new=args.gen, deadline_ms=deadline_ms)

    if cm is not None and not restored:
        # Calibration micro-run: one deadline-free request per compiled
        # shape seeds the per-phase cost table (and pre-compiles, so
        # workload estimates don't include trace time).
        calibrate(engine, [make_req(-1 - w, 0)
                           for w in range(2 * args.replicas)])
    if cm is not None:
        if args.asr:
            ke, kp, kd = cm.asr_keys(batchers[0])
            print(f"calibrated: encode chunk "
                  f"{(cm.cost(ke) or 0) * 1e3:.1f} ms, ", end="")
        else:
            kp, kd = cm.lm_keys(batchers[0])
            print("calibrated: ", end="")
        print(f"prefill chunk {(cm.cost(kp) or 0) * 1e3:.1f} ms, "
              f"decode token {(cm.cost(kd) or 0) * 1e3:.1f} ms")
    # Counter baselines so the summary reports workload quanta only
    # (the calibration micro-run above consumed some already).
    q0p = sum(b.prefill_quanta for b in batchers)
    q0d = sum(b.decode_quanta for b in batchers)
    submit_ts = {}
    for r in range(n_requests):
        submit_ts[r] = engine.bus.clock()
        engine.submit(make_req(r, r, deadline_ms=args.deadline_ms))
    t0 = time.time()
    done, ttft, rejected = [], {}, []
    for e in engine.stream():
        if isinstance(e, TokenDelta) and e.rid in submit_ts \
                and e.rid not in ttft:
            ttft[e.rid] = e.ts - submit_ts[e.rid]
        elif isinstance(e, Finished) and e.rid >= 0:
            done.append(e.result)
        elif isinstance(e, Rejected):
            rejected.append(e)
    dt = time.time() - t0
    n_tok = sum(len(d.prompt) + len(d.out) for d in done)
    enc = (f"{sum(b.encode_quanta for b in batchers)} encode + "
           if args.asr else "")
    hits = (f", {sum(b.audio_hits for b in batchers)} audio-cache hits"
            if args.asr else "")
    print(f"served {len(done)} requests / {n_tok} tokens in {dt:.2f}s "
          f"({enc}{sum(b.prefill_quanta for b in batchers) - q0p} prefill"
          f" + {sum(b.decode_quanta for b in batchers) - q0d} decode "
          f"quanta{hits})")
    if spec_decode is not None:
        prop = sum(b.spec_proposed for b in batchers)
        acc = sum(b.spec_accepted for b in batchers)
        print(f"speculation: {acc}/{prop} draft tokens accepted "
              f"({acc / max(1, prop):.0%}), "
              f"{sum(b.decode_launches for b in batchers)} target decode"
              f" launches, {sum(b.draft_launches for b in batchers)} "
              "draft launches")
    if args.replicas > 1:
        for rs in engine.stats()["replicas"]:
            print(f"  {rs['name']}: {rs['state']}, {rs['steps']} quanta")
    for e in rejected:
        print(f"rejected rid {e.rid} ({e.reason}): estimated "
              f"{e.estimated_s * 1e3:.1f} ms > budget "
              f"{e.budget_s * 1e3:.1f} ms")
    if ttft:
        print(f"ttft: first {min(ttft.values()):.2f}s / "
              f"worst {max(ttft.values()):.2f}s (incl. compile)")
    if done:
        print("first request:", done[0].prompt + done[0].out)
    if cm is not None and args.cost_model_path:
        cm.save(args.cost_model_path)
        print(f"cost model saved to {args.cost_model_path} "
              f"({len(cm.snapshot())} phase entries)")
    if tele is not None:
        if args.metrics_out:
            if args.metrics_out.endswith(".prom"):
                with open(args.metrics_out, "w") as f:
                    f.write(tele.registry.to_prometheus())
            else:
                tele.registry.write_snapshot(args.metrics_out)
            print(f"metrics snapshot written to {args.metrics_out} "
                  f"({len(tele.registry.instruments())} instruments)")
        if args.trace_out and tele.tracer is not None:
            tele.tracer.export(args.trace_out)
            print(f"trace written to {args.trace_out} "
                  f"({len(tele.tracer.spans)} spans, "
                  f"{len(tele.tracer.markers)} markers — load in "
                  f"Perfetto / chrome://tracing)")


if __name__ == "__main__":
    main()
