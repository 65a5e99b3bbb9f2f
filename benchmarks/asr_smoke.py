"""Gating ASR smoke: three-modality serving + enc-dec paged prefill.

Drives the ``AsrEngine`` (PR 9) as the third modality behind one
``EngineRouter`` and gates on the subsystem's core promises:

* **Three-modality stream** — one router multiplexing a
  ``DiffusionEngine``, an LM ``ContinuousBatcher``, and an
  ``AsrEngine`` over one shared bus keeps every per-rid lifecycle
  invariant from ``streaming_smoke`` intact, interleaves modalities
  (not three serial phases), and the audio prefix cache adopts a
  repeated audio chain (no re-encode for the duplicate).
* **Fused enc-dec prefill wins** — the fused paged decoder prefill
  emits bit-identical transcripts to the retained decode-step scan at
  strictly fewer kernel launches (the gated row leads with the launch
  count).
* **Failover without loss** — with 2 ASR replicas and one killed
  mid-encode by a deterministic ``FaultInjector``, every transcript is
  bit-identical to a single-replica run of the same seeds: migrated
  requests re-enter via ``Progress(phase="resume")``, re-adopting the
  published cross chain where one exists and re-encoding otherwise.

Run:  PYTHONPATH=src python benchmarks/asr_smoke.py [--json PATH]
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.base import ModelConfig, reduced
from repro.configs.whisper_large_v3 import config as WHISPER
from repro.engine import (TINY_SD, AsrEngine, DiffusionEngine, EngineRouter,
                          FaultInjector, Finished, FleetManager,
                          GenerateRequest, Progress, ReplicaSpec,
                          TranscribeRequest, init_pipeline)
from repro.models.frontend import synthetic_audio
from repro.models.transformer import init_lm
from repro.serving import ContinuousBatcher, Request

try:                          # package import (python -m ...)
    from benchmarks.streaming_smoke import check_event_invariants
except ImportError:           # script run: sys.path[0] is benchmarks/
    from streaming_smoke import check_event_invariants

ASR_CFG = reduced(WHISPER, d_model=64, head_dim=16, d_ff=128,
                  vocab_size=96, encoder_seq=32)
LM_CFG = ModelConfig(name="smoke-lm", family="dense", num_layers=2,
                     d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                     vocab_size=96, head_dim=16)

# Fleet faults are injected deterministically; the watchdog threshold
# is parked high so real CPU timing noise cannot evict a healthy
# replica and flake the gate.
NO_WATCHDOG = 1e9


def _audio(seed: int):
    return synthetic_audio(jax.random.PRNGKey(seed), ASR_CFG)


def _transcribe(rid: int, seed: int, max_new: int = 6):
    rng = np.random.RandomState(seed)
    return TranscribeRequest(rid=rid, audio=_audio(seed),
                             prompt=rng.randint(1, 90, size=5).tolist(),
                             max_new=max_new)


def _transcripts(log) -> dict:
    return {e.rid: list(e.result.out) for e in log
            if isinstance(e, Finished)
            and isinstance(e.result, TranscribeRequest)}


def smoke_three_modality_stream() -> list[str]:
    """One router, one bus, three engines: diffusion + LM + ASR."""
    sd_params = init_pipeline(jax.random.PRNGKey(0), TINY_SD)
    lm_params = init_lm(jax.random.PRNGKey(2), LM_CFG)
    asr_params = init_lm(jax.random.PRNGKey(0), ASR_CFG)
    toks = jax.random.randint(jax.random.PRNGKey(1), (TINY_SD.text_len,),
                              0, TINY_SD.clip_cfg().vocab_size)

    asr = AsrEngine(asr_params, ASR_CFG, slots=1, max_len=32,
                    audio_chunk=16, prefill_chunk=4)
    router = EngineRouter(
        diffusion=DiffusionEngine(sd_params, TINY_SD, max_batch=1),
        lm=ContinuousBatcher(lm_params, LM_CFG, slots=2, max_len=16),
        asr=asr)

    router.submit(GenerateRequest(rid=0, tokens=toks, sampler="ddim",
                                  steps=2, seed=0))
    router.submit(Request(rid=10, prompt=[3, 1, 4, 1, 5], max_new=6))
    router.submit(Request(rid=11, prompt=[2, 7, 1, 8], max_new=6))
    # rid 21 repeats rid 20's audio; with one ASR slot it queues until
    # 20 retires and must adopt the published cross chain.
    router.submit(_transcribe(20, seed=5))
    router.submit(_transcribe(21, seed=5))
    router.submit(_transcribe(22, seed=6))

    log = list(router.stream())
    rids = (0, 10, 11, 20, 21, 22)
    check_event_invariants(log, expect_finished=rids)
    out = _transcripts(log)
    assert out[20] == out[21], \
        f"adopted audio diverged: {out[21]} vs {out[20]}"
    assert asr.audio_hits >= 1, "repeated audio never hit the cache"
    assert asr.runtime.cross_prefix.hits > 0
    # Interleave: a non-ASR event must land inside the ASR event span.
    asr_ix = [i for i, e in enumerate(log) if e.rid >= 20]
    assert any(log[i].rid < 20 for i in range(asr_ix[0], asr_ix[-1])), \
        "stream did not interleave ASR with the other modalities"
    rows = [f"asr_smoke/three_modality,{len(rids)}/{len(rids)} terminal "
            f"on one bus,diffusion+lm+asr interleaved; "
            f"{asr.encode_quanta} encode quanta",
            f"asr_smoke/audio_cache,{asr.audio_hits} hit of 1 repeated "
            f"audio,adopted chain skipped "
            f"{-(-ASR_CFG.encoder_seq // 16)} encode quanta"]
    print(rows[0])
    print(rows[1])
    return rows


def smoke_fused_prefill_launches() -> list[str]:
    """Fused enc-dec decoder prefill: bit-exact vs the decode-step
    scan, strictly fewer launches (tight lower-better gate)."""
    params = init_lm(jax.random.PRNGKey(0), ASR_CFG)
    outs, launches = [], []
    for fused in (True, False):
        eng = AsrEngine(params, ASR_CFG, slots=1, max_len=32,
                        audio_chunk=32, prefill_chunk=4,
                        audio_share=False, fused_prefill=fused)
        assert eng.fused_prefill is fused
        reqs = [_transcribe(i, seed=3 + i, max_new=5) for i in range(2)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        outs.append([list(r.out) for r in reqs])
        launches.append(eng.prefill_launches)
    assert outs[0] == outs[1], \
        f"fused prefill diverged from scan: {outs[0]} vs {outs[1]}"
    assert launches[0] < launches[1], \
        f"fused did not reduce launches: {launches[0]} vs {launches[1]}"
    rows = [f"asr_smoke/fused_prefill,{launches[0]} launches,"
            f"scan {launches[1]}; transcripts bit-exact"]
    print(rows[0])
    return rows


def smoke_fleet_failover_bit_exact() -> list[str]:
    """2 ASR replicas, one killed mid-run: zero loss, transcripts
    bit-identical to a single-replica run of the same seeds."""
    params = init_lm(jax.random.PRNGKey(0), ASR_CFG)

    def build():
        return AsrEngine(params, ASR_CFG, slots=2, max_len=32,
                         audio_chunk=16, prefill_chunk=4)

    def workload():
        return [_transcribe(i, seed=10 + i) for i in range(6)]

    ref = FleetManager([ReplicaSpec("solo", build)],
                       watchdog_threshold=NO_WATCHDOG)
    for r in workload():
        ref.submit(r)
    ref_out = _transcripts(ref.stream())
    assert len(ref_out) == 6

    fleet = FleetManager([ReplicaSpec(f"r{i}", build) for i in range(2)],
                         injector=FaultInjector().kill("r1", 3),
                         watchdog_threshold=NO_WATCHDOG)
    for r in workload():
        fleet.submit(r)
    log = list(fleet.stream())
    stats = fleet.stats()

    check_event_invariants(log, expect_finished=tuple(ref_out))
    out = _transcripts(log)
    assert not stats["lost"], f"lost requests: {stats['lost']}"
    assert out == ref_out, \
        f"transcripts diverged after migration: {out} vs {ref_out}"
    assert stats["migrations"] > 0, \
        "kill landed on an idle replica: smoke exercised nothing"
    resumed = {e.rid for e in log
               if isinstance(e, Progress) and e.phase == "resume"}
    assert resumed, "no Progress(resume) after eviction"
    rows = [f"asr_smoke/failover,6/6 bit-exact across replica kill,"
            f"{stats['migrations']} migrated "
            f"({sorted(resumed)} resumed) 0 lost"]
    print(rows[0])
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="append machine-readable rows to the suite's "
                         "perf-trajectory record (benchmarks/common.py "
                         "schema)")
    a = ap.parse_args()
    all_rows = (smoke_three_modality_stream()
                + smoke_fused_prefill_launches()
                + smoke_fleet_failover_bit_exact())
    if a.json:
        try:
            from benchmarks.common import write_bench_json
        except ImportError:
            from common import write_bench_json
        write_bench_json(a.json, "serving", all_rows, bench="asr_smoke")
