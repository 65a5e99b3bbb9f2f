"""End-to-end metrics, from the window's host-clock records.

* ``setup_s``: process start to the end of warm-up;
* ``images_per_s``: images completed in the window over the time from
  the window's start to the last completion in it.  The closed loop
  starts the window at a dispatch and batches end together, so the time
  after the last completion holds only work that is not counted either;
* ``latency_p50_s`` / ``latency_p95_s``: from each request's due time to
  its image being ready on the device, over every request due in the
  window (those still running at the close are waited for); a request
  that failed counts with the time until it was given up.
"""
from __future__ import annotations

import numpy as np


def latencies(win) -> np.ndarray:
    out = []
    for rec in win.rec.values():
        end = rec.get("ready", win.t_given_up)
        out.append(end - rec["due"])
    return np.asarray(out, np.float64)


def images_per_s(win) -> float | None:
    done = [rec["ready"] for rec in win.rec.values()
            if "ready" in rec and rec["ready"] <= win.t_close]
    if not done:
        return None
    return len(done) / (max(done) - win.t0)


def compute(name: str, win, setup_s: float) -> float | None:
    if name == "setup_s":
        return setup_s
    if name == "images_per_s":
        return images_per_s(win)
    if name in ("latency_p50_s", "latency_p95_s"):
        lat = latencies(win)
        if not len(lat):
            return None
        return float(np.percentile(lat, 50 if name.endswith("p50_s")
                                   else 95))
    raise KeyError(f"no end-to-end metric {name!r}")
