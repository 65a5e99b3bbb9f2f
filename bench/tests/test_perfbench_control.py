"""The control: the reference one precision step below the
configuration (weights one format down, int8 matmul inputs) must read
above the cell's limit, where the program reads below it (tiny cells, so
that a CPU test run holds them; the full-size readings are in PERF.md)."""
import json
import os

import numpy as np
import pytest

from perfbench_fixtures import load, tiny_spec


@pytest.mark.parametrize("policy,mix,limit", [
    ("q8_0", "turbo-poisson", 0.05), ("q3_k", "cfg20-offline", 0.1)])
def test_control_fails_where_the_program_passes(tiny_root, policy, mix,
                                                limit):
    import jax
    from harness import check, traffic, weights
    import sd15
    bdir = os.path.join(tiny_root, "bench")
    reference = load(os.path.join(bdir, "configs", "sd15_reference.py"),
                     "perfbench_reference")
    spec = tiny_spec(policy)
    with open(os.path.join(bdir, "traffic", f"tiny-{mix}.json")) as f:
        m = json.load(f)
    from repro.engine import (DiffusionEngine, DiffusionEngineConfig,
                              EngineConfig)
    cfg = sd15.program_config(spec)
    make = weights.maker(lambda: sd15.layout(cfg), sd15.is_linear)
    for i, seed in enumerate((11, 12, 2 ** 40 + 13)):
        key = weights.seed_key(seed)
        params = sd15.quantize(make(key), policy)
        engine = DiffusionEngine(params, cfg, config=EngineConfig(
            diffusion=DiffusionEngineConfig(max_batch=2)))
        reqs = traffic.requests(m, spec, seed, 2, rid0=2 * i)
        for r in reqs:
            engine.submit(sd15.request(r))
        got = {res.rid: np.asarray(jax.device_get(res.image), np.float32)
               for res in engine.run()}
        refs = check.reference_images(sd15, reference, spec, make, key,
                                      reqs, ("f32", "lower"))
        ref, low = refs["f32"], refs["lower"]
        for r in reqs:
            assert check.rel_err(got[r["rid"]], ref[r["rid"]]) < limit
            assert check.rel_err(low[r["rid"]], ref[r["rid"]]) > limit
