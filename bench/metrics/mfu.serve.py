"""Useful model operations of the traced program executions over their
device time at the chip's bf16 peak, in %.  Useful: CLIP, the UNet
evaluations the request asks for and the VAE, for rows that carried a
request (padded rows and padding steps do not count).  The executions
are those lying wholly in the traced window; their rows are the mean
rows of the batches dispatched after the trace started and ready by the
close (the host sees the last one ready a moment after the device
ends it)."""
from harness import layers


def read(run):
    tr = run.trace
    batches = layers.traced_batches(run)
    if not tr or not tr["modules"] or not batches:
        return None
    rows = sum(b["rows"] for b in batches) / len(batches) * tr["modules"]
    dev_s = sum(tr["module_s"]) / len(tr["module_s"])
    return 100.0 * rows * layers.request_flops(run) / (
        dev_s * run.peak["bf16_flops_per_s"])
