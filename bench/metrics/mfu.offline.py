"""Useful model operations of the images completed in the traced
stretch over its length at the chip's bf16 peak, in %."""
from harness import layers


def read(run):
    tr, win = run.trace, run.win
    t0 = getattr(win, "trace_t0", None)
    if not tr or t0 is None:
        return None
    done = sum(1 for r in win.rec.values()
               if t0 <= r.get("ready", -1.0) <= win.t_close)
    if not done:
        return None
    return 100.0 * done * layers.request_flops(run) / (
        tr["window_s"] * run.peak["bf16_flops_per_s"])
