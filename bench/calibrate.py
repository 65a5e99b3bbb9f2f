"""Readings that a cell's correctness limit is set from, at the cell's own
size, in one process (run on the chip when a limit is set; the
benchmark's runs never run the control).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3]

For each seed: the program (weights from the seed, the configuration's
quantization, the engine at the cell's ``max_batch``) serves the
requests a run would sample first, in full batches, and each image is
compared with the reference (``image_rel_err``, the number a run
compares).  For each control seed the control (the reference one
precision step below the configuration, see the family's reference
module) is compared with the reference in the same way.  One JSON line
per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import run


def main(argv=None, *, root: str = run.ROOT,
         require_tpu: bool = True) -> list[dict]:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    c = run.load_cell(root, args.workload)
    if require_tpu:
        run.find_device(c["cell"]["chips"])
    run.enable_cache(root)
    sys.path.insert(1, os.path.join(root, "src"))
    import jax
    import numpy as np
    from harness import check, traffic
    spec, mix = c["spec"], c["mix"]
    cdir = os.path.join(c["bench_dir"], "configs")
    family = run.load_module(os.path.join(cdir, spec["family"] + ".py"),
                             "family")
    reference = run.load_module(os.path.join(cdir, spec["reference"]),
                                "reference")
    k = c["checks"]["sample"]
    (number,) = c["checks"]["numbers"]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    todo = sorted(set(seeds) | control)
    reqs = {seed: traffic.requests(mix, spec, seed, k, rid0=i * k)
            for i, seed in enumerate(todo)}
    # The program first, for every seed; its state is freed before the
    # reference runs, as in a run.
    got, engine = {}, None
    for seed in seeds:
        prog = run.Program(c, seed, family)
        if engine is None:
            engine = prog.engine(mix["max_batch"])
        engine.params = prog.params
        del prog
        for r in reqs[seed]:
            engine.submit(family.request(r))
        got.update({res.rid: np.asarray(jax.device_get(res.image),
                                        np.float32)
                    for res in engine.run()})
        engine.finished.clear()
    del engine
    rows = []
    for seed in todo:
        t = time.perf_counter()
        make, key = run.Program.weights(c, seed, family)
        modes = check.modes_for(number) + (("lower",) if seed in control
                                           else ())
        refs = check.reference_images(family, reference, spec, make, key,
                                      reqs[seed], modes)
        row = {"workload": args.workload, "seed": seed, "number": number}
        if seed in seeds:
            row["program"] = list(check.numbers(number, got, refs).values())
        if seed in control:
            row["control"] = list(check.numbers(number, refs["lower"],
                                                refs).values())
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


if __name__ == "__main__":
    main()
