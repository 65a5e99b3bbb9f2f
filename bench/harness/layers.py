"""Arithmetic shared by the per-layer metric readers.

Every number is from shapes (the configuration family's ``*_cost``
module), the window's host records and the reduced trace; a reader
returns ``None`` where the trace holds nothing to read, never 0.
"""
from __future__ import annotations

import numpy as np

from harness import traffic


def p95(values) -> float | None:
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, 95)) if len(v) else None


def request(run) -> dict:
    """The mix's request shape, as a generated request."""
    rq = run.mix["request"]
    return {"sampler": rq["sampler"], "steps": rq["steps"],
            "guidance": rq["guidance"],
            "neg_tokens": [] if rq["negative_prompt"] else None}


def branches(run) -> int:
    return 2 if traffic.uses_cfg(request(run)) else 1


def request_flops(run) -> float:
    r = request(run)
    return run.cost.request_flops(run.spec, r["steps"], traffic.uses_cfg(r))


def traced_batches(run) -> list[dict]:
    """Batches dispatched after the trace started whose images were all
    ready by the close: the program executions the trace holds whole."""
    win = run.win
    t0 = getattr(win, "trace_t0", None)
    if t0 is None:
        return []
    out = []
    for b in win.batches:
        ready = [win.rec[r].get("ready") for r in b["rids"]]
        if b["dispatch"] >= t0 and ready and all(
                x is not None and x <= win.t_close for x in ready):
            out.append(b)
    return out


def per_module(run) -> dict | None:
    """Sites of one program execution: ``{"clip", "unet", "vae",
    "unet_evals"}``.  UNet evaluations run per execution are counted
    from the flash-attention events (each evaluation runs one per
    transformer attention, each prompt encoding one per CLIP layer);
    ``None`` if the count is not whole or the quantized matmul kernels'
    events do not number what those sites call."""
    tr = run.trace
    if not tr or not tr["modules"]:
        return None
    mb = run.mix["max_batch"]
    cost, spec = run.cost, run.spec
    clip = cost.clip_sites(spec, mb)
    unet = cost.unet_sites(spec, mb)
    n_clip = len(cost.attention_calls(clip)) * branches(run)
    per_unet = len(cost.attention_calls(unet))
    flash = tr["kernel_n"].get("flash_attention", 0) / tr["modules"]
    evals = (flash - n_clip) / per_unet
    if evals <= 0 or abs(evals - round(evals)) > 1e-9:
        return None
    pm = {"clip": clip * branches(run), "unet": unet,
          "unet_evals": int(round(evals)), "vae": cost.vae_sites(spec, mb)}
    # The quantized matmul kernel's events must agree with that count.
    sites = pm["clip"] + pm["unet"] * pm["unet_evals"] + pm["vae"]
    for kernel, fmt in MATMUL.items():
        n = tr["kernel_n"].get(kernel, 0)
        if n != len(cost.matmul_calls(spec, sites, fmt)) * tr["modules"]:
            return None
    return pm


MATMUL = {"q8_matmul": "q8_0", "q3k_matmul": "q3_k"}


def kernel_roofline(run, kernel: str) -> float | None:
    """Least time of the kernel's calls at the chip's peaks over the
    device time its events took, in %; ``None`` where the trace shows
    no such kernel or a call count that the shapes do not give."""
    tr = run.trace
    pm = per_module(run)
    if pm is None or not tr["kernel_n"].get(kernel):
        return None
    cost, spec, peak = run.cost, run.spec, run.peak
    pf, bw = peak["bf16_flops_per_s"], peak["hbm_bytes_per_s"]
    sites = pm["clip"] + pm["unet"] * pm["unet_evals"] + pm["vae"]
    if kernel == "flash_attention":
        calls = cost.attention_calls(sites)
        least = cost.attention_min_seconds(calls, pf, bw)[0]
    else:
        fmt = MATMUL[kernel]
        calls = cost.matmul_calls(spec, sites, fmt)
        least = cost.matmul_min_seconds(calls, fmt, pf, bw)[0]
    if len(calls) * tr["modules"] != tr["kernel_n"][kernel]:
        return None
    return 100.0 * least * tr["modules"] / tr["kernel_s"][kernel]
