"""Helpers of the benchmark's CPU tests: a tiny configuration of the
SD v1.5 family and a checkout that holds tiny cells of each mix."""
import json
import os
import shutil

from perfbench_paths import BENCH, ROOT

TINY = {
    "unet": {"in_channels": 4, "out_channels": 4,
             "block_out_channels": [32, 64], "layers_per_block": 1,
             "attention_levels": [0, 1], "num_heads": 2,
             "cross_attention_dim": 64, "norm_num_groups": 8,
             "time_embed_dim": 128},
    "vae": {"latent_channels": 4, "out_channels": 3,
            "block_out_channels": [32, 64], "layers_per_block": 1,
            "norm_num_groups": 8, "scaling_factor": 0.18215},
    "text_encoder": {"hidden_size": 64, "num_hidden_layers": 2,
                     "num_attention_heads": 2, "intermediate_size": 256,
                     "vocab_size": 512, "max_position_embeddings": 77},
    "latent_hw": 8,
}


def tiny_spec(policy: str) -> dict:
    """A configuration file of the SD v1.5 family at the CPU tests'
    size (the program's TINY_SD widths) under ``policy``."""
    with open(os.path.join(BENCH, "configs", f"sd15-{policy}.json")) as f:
        spec = json.load(f)
    spec.update(json.loads(json.dumps(TINY)))
    spec["name"] = f"tiny-{policy}"
    return spec


def make_tiny_root(tmp_path) -> str:
    """A checkout holding the benchmark plus tiny cells of each mix."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for policy in ("q8_0", "q3_k"):
        spec = tiny_spec(policy)
        path = f"bench/configs/{spec['name']}.json"
        (tmp_path / path).write_text(json.dumps(spec))
        bench["configs"].append({"name": spec["name"], "source": "tests",
                                 "file": path, "reduced": [], "why": "tests"})
    mixes = {"turbo-poisson": {"rate_per_s": 20.0, "trace_seconds": 1.0},
             "cfg20-offline": {"trace_seconds": 2.0,
                               "request": {"steps": 3}}}
    for mix, over in mixes.items():
        with open(tmp_path / "bench" / "traffic" / f"{mix}.json") as f:
            m = json.load(f)
        m.update({k: v for k, v in over.items() if k != "request"})
        m["request"].update(over.get("request", {}))
        (tmp_path / "bench" / "traffic" / f"tiny-{mix}.json").write_text(
            json.dumps(m))
    # Limits at this size, from CPU readings of the program (at most
    # 0.011 one-step, 0.053 three-step CFG) and of the control (at least
    # 0.09 and 0.17).
    for cell, conf, mix, limit in (
            ("tiny.turbo", "tiny-q8_0", "tiny-turbo-poisson", 0.05),
            ("tiny.offline", "tiny-q3_k", "tiny-cfg20-offline", 0.1)):
        bench["workloads"].append({"name": cell, "config": conf,
                                   "traffic": mix, "chips": 1,
                                   "why": "tests"})
        (tmp_path / "bench" / "checks" / f"{cell}.json").write_text(
            json.dumps({"sample": 2, "numbers": {
                "image_rel_err": {"limit": limit}}}))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            kind = "tiny.turbo" if any("turbo" in w for w in m["workloads"]) \
                else "tiny.offline"
            m["workloads"].append(kind)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def load(path: str, name: str):
    import importlib.util
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_cell(root: str, capsys, workload: str, *, trace: int = 0,
             seconds: float = 2.0, seed: int = 98765432101) -> dict:
    """One run of ``bench/run.py`` in-process at the tests' size, past
    the look for a chip; returns its result line.  JAX's compile-cache
    settings are put back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_compilation_cache_max_size")
    saved = {n: getattr(jax.config, n) for n in names}
    run = load(os.path.join(BENCH, "run.py"), "perfbench_run")
    try:
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, require_tpu=False)
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        cc.reset_cache()
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
