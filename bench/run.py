"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  Everything is found by name from
``BENCHMARK.json``: the cell's configuration file, its traffic mix
``bench/traffic/<mix>.json``, its correctness limits
``bench/checks/<cell>.json``, the configuration family's modules
``bench/configs/<family>.py`` (bridge to the program),
``<family>_cost.py`` (operations and bytes from shapes) and its plain
reference, the per-layer metric readers ``bench/metrics/<metric>.py``
and the device peaks ``bench/peaks.json``.

A run: checks for the chip (none, or fewer than the cell asks: exit 2,
no result); set-up (seeded weights made on the device, the program's
model-file quantization, the engine, one warm-up batch of the cell's own
shape: ``setup_s``); the window of ``--seconds`` through
``DiffusionEngine.submit()``/``step()``; then, with the program freed,
the reference over a sample of the window's requests drawn from the
seed.  ``--trace 1`` profiles the end of the window and prints the
per-layer metrics in place of the end-to-end ones.

The last lines on standard error are each compared number with its
limit; the last line on standard output is the JSON result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: str, workload: str) -> dict:
    """Everything the cell names, read from files under ``root``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        spec = json.load(f)
    bdir = os.path.join(root, "bench")
    with open(os.path.join(bdir, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    with open(os.path.join(bdir, "checks", workload + ".json")) as f:
        checks = json.load(f)
    with open(os.path.join(bdir, "peaks.json")) as f:
        peaks = json.load(f)

    def ours(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if ours(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if ours(m) and m["moves"] in names]
    return {"cell": cell, "spec": spec, "mix": mix,
            "checks": checks, "peaks": peaks, "end_to_end": e2e,
            "per_layer": layer, "bench_dir": bdir}


def find_device(chips: int):
    """The accelerator, or exit 2 with no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"run.py: needs {chips} TPU chip(s), JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(2)
    return devs


def enable_cache(root: str) -> None:
    """JAX's persistent compile cache at one fixed path in the
    checkout, for every program however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # No eviction: an entry then needs no access-time file beside it.
    jax.config.update("jax_compilation_cache_max_size", -1)


class Program:
    """The system under test for one configuration: seeded weights,
    the program's quantization, and an engine."""

    def __init__(self, c: dict, seed: int, family):
        import jax
        self.cfg = family.program_config(c["spec"])
        t = time.perf_counter()
        self.make, self.key = self.weights(c, seed, family)
        fl = jax.block_until_ready(self.make(self.key))
        self.weights_s = time.perf_counter() - t
        t = time.perf_counter()
        self.params = family.quantize(fl, c["spec"]["policy"])
        del fl
        jax.block_until_ready(self.params)
        self.quantize_s = time.perf_counter() - t

    @staticmethod
    def weights(c: dict, seed: int, family):
        """``(make, key)``: the seeded float weights' program and key."""
        from harness import weights
        cfg = family.program_config(c["spec"])
        return (weights.maker(lambda: family.layout(cfg), family.is_linear),
                weights.seed_key(seed))

    def engine(self, max_batch: int):
        from repro.engine import (DiffusionEngine, DiffusionEngineConfig,
                                  EngineConfig)
        return DiffusionEngine(self.params, self.cfg, config=EngineConfig(
            diffusion=DiffusionEngineConfig(max_batch=max_batch)))


def warm_up(engine, family, mix, spec, seed, reqs) -> None:
    """One full batch of each kind of request in ``reqs`` through
    submit/step: the programs the window runs, and the per-row result
    slices, and no others."""
    import jax
    from harness import traffic
    mb = mix["max_batch"]
    kinds = {}
    for r in reqs:
        kinds.setdefault(traffic.kind(r), r)
    rid = -1
    for r in kinds.values():
        for _ in range(mb):
            engine.submit(family.request(dict(r, rid=rid)))
            rid -= 1
        while engine.has_work():
            engine.step()
        jax.block_until_ready([res.image for res in engine.finished])
        engine.finished.clear()


class Tracer:
    """Starts the profiler at the first batch boundary after
    ``start_at`` (host clock); ``stop()`` ends it after the close."""

    def __init__(self, win, start_at_s: float):
        self.win = win
        self.start_at_s = start_at_s
        self.dir = None

    def __call__(self, now: float) -> None:
        import jax
        if self.dir is None and now >= self.win.t0 + self.start_at_s:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.win.trace_t0 = time.perf_counter()
            self.win.spans.on = True

    def stop(self):
        import jax
        if self.dir is None:
            return None
        self.win.spans.close()
        self.win.spans.on = False
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        return paths[0] if paths else None


def device_record(devs, chips: int) -> dict:
    """The device as JAX reports it.  Peak memory is the peak of arrays
    in use plus the peak reserved for programs' temporaries (a program
    reserves its temporaries when loaded)."""
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips, "memory_peak_bytes": peak}


def sample_rids(win, seed: int, k: int) -> list[int]:
    """``k`` requests drawn from the seed among those whose image came."""
    import numpy as np
    from harness.weights import key_words
    done = sorted(win.images)
    rng = np.random.default_rng(key_words(seed, "sample"))
    k = min(k, len(done))
    return sorted(int(r) for r in rng.choice(done, size=k, replace=False))


def main(argv=None, *, root: str = ROOT, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    c = load_cell(root, args.workload)
    chips = c["cell"]["chips"]
    if require_tpu:
        devs = find_device(chips)
    else:
        import jax
        devs = jax.devices()
    enable_cache(root)
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(1, src)

    import jax
    import numpy as np
    from harness import check, endtoend, trace as trace_mod, traffic

    spec, mix = c["spec"], c["mix"]
    cdir = os.path.join(c["bench_dir"], "configs")
    family = load_module(os.path.join(cdir, spec["family"] + ".py"),
                         "family_" + spec["family"])
    cost = load_module(os.path.join(cdir, spec["family"] + "_cost.py"),
                       "cost_" + spec["family"])
    reference = load_module(os.path.join(cdir, spec["reference"]),
                            "reference_" + spec["family"])

    # ------------------------------------------------------- set-up
    open_loop = mix["loop"] == "open"
    if open_loop:
        due = traffic.arrivals(mix, args.seconds)
        reqs = traffic.requests(mix, spec, args.seed, len(due))
    else:
        due = None
        reqs = traffic.requests(mix, spec, args.seed, mix["max_requests"])
    t_ready = time.perf_counter()
    prog = Program(c, args.seed, family)
    engine = prog.engine(mix["max_batch"])
    t = time.perf_counter()
    warm_up(engine, family, mix, spec, args.seed, reqs)
    setup_s = time.perf_counter() - T_PROCESS
    print(f"setup_s {setup_s!r}: to the chip {t_ready - T_PROCESS!r}, "
          f"weights {prog.weights_s!r}, quantization {prog.quantize_s!r}, "
          f"warm-up {time.perf_counter() - t!r}", file=sys.stderr,
          flush=True)

    # ------------------------------------------------------- window
    from harness.serve import Window
    win = Window(engine, family.request, reqs, due, args.seconds,
                 queue=mix.get("queue", 0))
    tracer = None
    if args.trace:
        tracer = Tracer(win, max(0.0, args.seconds - mix["trace_seconds"]))
        win.tracer = tracer
    traces0 = engine.traces
    win.run()
    path = tracer.stop() if tracer else None
    win.drain(open_loop)
    retraced = engine.traces - traces0
    attempted = len(win.rec)
    device = device_record(devs, chips)

    # --------------------------------------------------- metrics
    metrics = {}
    breakdown = run = None
    if args.trace:
        tr = trace_mod.reduce(path, chips) if path else None
        if tracer and tracer.dir:
            shutil.rmtree(tracer.dir, ignore_errors=True)
        if tr is not None:
            print("trace: " + json.dumps(
                {k: tr[k] for k in ("window_s", "busy_s", "modules",
                                    "module_s", "kernel_n", "kernel_s")}),
                file=sys.stderr)
        run = trace_mod.RunView(win=win, trace=tr, spec=spec, mix=mix,
                                cost=cost, peaks=c["peaks"],
                                kind=device["kind"])
        for m in c["per_layer"]:
            reader = load_module(os.path.join(c["bench_dir"], "metrics",
                                              m["name"] + ".py"),
                                 "metric_" + m["name"].replace(".", "_"))
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if tr is not None:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            breakdown = tr["breakdown"]
    else:
        for m in c["end_to_end"]:
            v = endtoend.compute(m["name"], win, setup_s)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # ------------------------------------------------ correctness
    failed = win.failed
    rids = sample_rids(win, args.seed, c["checks"]["sample"])
    by_rid = {r["rid"]: r for r in reqs}
    got = {rid: np.asarray(jax.device_get(win.images[rid]), np.float32)
           for rid in rids}
    make, key = prog.make, prog.key
    del win, engine, prog, tracer, run
    gc.collect()
    print(f"window_traces {retraced}", file=sys.stderr)
    (number,) = c["checks"]["numbers"]
    readings = check.readings(family, reference, spec, make, key,
                              [by_rid[rid] for rid in rids], got, number)
    verdict = check.judge(readings, c["checks"])
    for line in verdict["lines"]:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    out = {"correct": verdict["correct"], "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = verdict["checks"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
