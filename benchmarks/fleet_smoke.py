"""Gating fleet smoke: N-replica scaling + zero-loss failover.

Drives a ``FleetManager`` fronting in-process engine replicas (each an
``EngineRouter`` over a ``DiffusionEngine`` and an LM
``ContinuousBatcher``) and gates on the fleet subsystem's three core
promises:

* **Failover without loss** — with 3 replicas and one replica killed
  mid-run by a deterministic ``FaultInjector``, every admitted request
  still reaches a terminal event, and every finished request's output
  (LM token sequence / diffusion image) is **bit-identical** to a
  single-replica run of the same seeds: LM requests resume via
  re-prefill of prompt + generated-so-far, diffusion requests rerun
  from their seed.  Migrated requests re-enter via
  ``Progress(phase="resume")`` — never a second ``Admitted``.
* **Event-ordering invariants survive the fleet** — the per-rid
  lifecycle invariants asserted by ``streaming_smoke`` hold on the one
  shared bus even across an eviction + migration.
* **Capacity recovers** — with ``replace_evicted=True`` (PR 9) the
  same kill respawns a fresh replica from the evicted spec's build:
  the fleet ends the run at full replica strength, the replacement
  absorbs real quanta, and every transcript stays bit-exact.
* **Throughput scales** — on a mixed LM workload, the 3-replica
  parallel makespan (the max over replicas of quanta each ran — wall
  time in a real deployment where replicas step concurrently) is
  strictly below the 1-replica makespan, i.e. 3-replica req/s exceeds
  1-replica req/s.

Run:  PYTHONPATH=src python benchmarks/fleet_smoke.py [--json PATH]
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.engine import (TINY_SD, DiffusionEngine, EngineRouter,
                          FaultInjector, Finished, FleetManager,
                          GenerateRequest, Progress, ReplicaSpec,
                          init_pipeline)
from repro.models.transformer import init_lm
from repro.serving import ContinuousBatcher, Request

try:                          # package import (python -m ...)
    from benchmarks.streaming_smoke import check_event_invariants
except ImportError:           # script run: sys.path[0] is benchmarks/
    from streaming_smoke import check_event_invariants

LM_CFG = ModelConfig(name="smoke-lm", family="dense", num_layers=2,
                     d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                     vocab_size=96, head_dim=16)

# Kill/slow detection is exercised deterministically via the injector;
# the watchdog threshold is parked high so real CPU timing noise
# (compiles landing at different quanta per replica) cannot evict a
# healthy replica and flake the gate.
NO_WATCHDOG = 1e9


def _params():
    sd_params = init_pipeline(jax.random.PRNGKey(0), TINY_SD)
    lm_params = init_lm(jax.random.PRNGKey(2), LM_CFG)
    return sd_params, lm_params


def _mixed_workload():
    """Mixed, seed-determined workload: rids 0-3 diffusion (one with
    preview streaming, so a segmented in-flight batch can be caught by
    the eviction), rids 10-17 LM."""
    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (TINY_SD.text_len,), 0,
                              TINY_SD.clip_cfg().vocab_size)
    reqs = [GenerateRequest(rid=i, tokens=toks, sampler="ddim", steps=2,
                            seed=i, preview_every=1 if i == 3 else 0)
            for i in range(4)]
    rng = np.random.RandomState(7)
    reqs += [Request(rid=10 + i,
                     prompt=rng.randint(1, 90, size=4).tolist(),
                     max_new=5)
             for i in range(8)]
    return reqs


def _outputs(log) -> dict:
    """rid -> comparable terminal payload (token list / image array)."""
    out = {}
    for e in log:
        if isinstance(e, Finished):
            r = e.result
            out[e.rid] = (list(r.out) if hasattr(r, "out")
                          else np.asarray(r.image))
    return out


def smoke_failover_bit_exact() -> list[str]:
    sd_params, lm_params = _params()

    def build():
        return EngineRouter(
            diffusion=DiffusionEngine(sd_params, TINY_SD, max_batch=2),
            lm=ContinuousBatcher(lm_params, LM_CFG, slots=2, max_len=32,
                                 fused_prefill=False))

    # Single-replica reference run of the same seeds.
    ref = FleetManager([ReplicaSpec("solo", build)],
                       watchdog_threshold=NO_WATCHDOG)
    for req in _mixed_workload():
        ref.submit(req)
    ref_out = _outputs(ref.stream())
    assert len(ref_out) == 12, f"reference lost requests: {ref_out.keys()}"

    # 3 replicas, one killed mid-run (its 3rd quantum: work is in
    # flight and partly decoded by then).
    fleet = FleetManager([ReplicaSpec(f"r{i}", build) for i in range(3)],
                         injector=FaultInjector().kill("r1", 3),
                         watchdog_threshold=NO_WATCHDOG)
    for req in _mixed_workload():
        fleet.submit(req)
    log = list(fleet.stream())
    stats = fleet.stats()

    by_rid = check_event_invariants(log, expect_finished=tuple(ref_out))
    out = _outputs(log)
    assert not stats["lost"], f"lost requests: {stats['lost']}"
    assert set(out) == set(ref_out), \
        f"terminal set mismatch: {set(out) ^ set(ref_out)}"
    for rid, want in ref_out.items():
        got = out[rid]
        if isinstance(want, list):
            assert got == want, f"rid {rid}: tokens diverged after " \
                f"migration: {got} vs {want}"
        else:
            assert np.array_equal(np.asarray(got), want), \
                f"rid {rid}: image not bit-identical after migration"
    assert ("r1", "injected kill of r1 at step 3") in stats["evictions"]
    assert stats["migrations"] > 0, \
        "kill landed on an idle replica: smoke exercised nothing"
    resumed = {e.rid for e in log
               if isinstance(e, Progress) and e.phase == "resume"}
    assert resumed, "no Progress(resume) after eviction"
    del by_rid
    rows = [f"fleet_smoke/failover,12/12 bit-exact across replica kill,"
            f"{stats['migrations']} migrated ({sorted(resumed)} resumed) "
            f"0 lost"]
    print(rows[0])
    return rows


def smoke_capacity_recovery() -> list[str]:
    """Replacement (PR 9): with ``replace_evicted=True`` an injected
    kill respawns a fresh replica from the evicted spec's build — the
    fleet ends the run at full strength, the replacement absorbs real
    work, and every request still finishes bit-exact."""
    _, lm_params = _params()
    n_replicas, n_req = 3, 18

    def build():
        return ContinuousBatcher(lm_params, LM_CFG, slots=2, max_len=16,
                                 fused_prefill=False)

    def reqs():
        rng = np.random.RandomState(11)
        return [Request(rid=i, prompt=rng.randint(1, 90, size=4).tolist(),
                        max_new=5)
                for i in range(n_req)]

    ref = FleetManager([ReplicaSpec("solo", build)],
                       watchdog_threshold=NO_WATCHDOG)
    for r in reqs():
        ref.submit(r)
    ref_out = _outputs(ref.stream())

    fleet = FleetManager(
        [ReplicaSpec(f"c{i}", build) for i in range(n_replicas)],
        injector=FaultInjector().kill("c1", 2),
        watchdog_threshold=NO_WATCHDOG, replace_evicted=True)
    for r in reqs():
        fleet.submit(r)
    out = _outputs(fleet.stream())
    stats = fleet.stats()

    assert out == ref_out, "replacement run diverged from reference"
    assert not stats["lost"], f"lost requests: {stats['lost']}"
    assert ("c1", "c1~0") in stats["replacements"], stats["replacements"]
    live = [r for r in stats["replicas"] if r["state"] != "EVICTED"]
    assert len(live) == n_replicas, \
        f"capacity not recovered: {len(live)}/{n_replicas} live replicas"
    repl = next(r for r in stats["replicas"] if r["name"] == "c1~0")
    assert repl["steps"] > 0, "replacement replica absorbed no work"
    rows = [f"fleet_smoke/capacity_recovery,{len(live)}/{n_replicas} "
            f"replicas live after kill,replacement c1~0 ran "
            f"{repl['steps']} quanta; {n_req}/{n_req} bit-exact"]
    print(rows[0])
    return rows


def smoke_throughput_scaling() -> list[str]:
    """Parallel makespan (max per-replica quanta — wall time when
    replicas step concurrently) must strictly drop from 1 to 3
    replicas on the same workload, i.e. fleet req/s scales."""
    _, lm_params = _params()
    n_req = 12

    def makespan(n_replicas: int) -> int:
        def build():
            return ContinuousBatcher(lm_params, LM_CFG, slots=2,
                                     max_len=16, fused_prefill=False)
        fleet = FleetManager(
            [ReplicaSpec(f"n{i}", build) for i in range(n_replicas)],
            watchdog_threshold=NO_WATCHDOG)
        rng = np.random.RandomState(3)
        for i in range(n_req):
            fleet.submit(Request(
                rid=i, prompt=rng.randint(1, 90, size=4).tolist(),
                max_new=5))
        done = fleet.run()
        assert len(done) == n_req
        return max(r["steps"] for r in fleet.stats()["replicas"])

    m1, m3 = makespan(1), makespan(3)
    # req/s at a nominal 10 ms quantum, for the human-readable detail;
    # the value leads with the speedup ratio.
    rps1, rps3 = n_req / (m1 * 0.01), n_req / (m3 * 0.01)
    rows = [f"fleet_smoke/scaling,{rps3 / rps1:.2f}x speedup at 3 "
            f"replicas,makespan {m3} quanta vs {m1}; "
            f"req/s {rps3:.0f} vs {rps1:.0f}"]
    print(rows[0])
    assert m3 < m1, (
        f"3-replica fleet must beat 1 replica on parallel makespan "
        f"(got {m3} vs {m1} quanta)")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="append machine-readable rows to the suite's "
                         "perf-trajectory record (benchmarks/common.py "
                         "schema)")
    a = ap.parse_args()
    all_rows = (smoke_failover_bit_exact() + smoke_capacity_recovery()
                + smoke_throughput_scaling())
    if a.json:
        try:
            from benchmarks.common import write_bench_json
        except ImportError:
            from common import write_bench_json
        write_bench_json(a.json, "serving", all_rows, bench="fleet_smoke")
