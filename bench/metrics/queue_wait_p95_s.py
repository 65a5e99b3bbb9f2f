"""95th percentile of the engine's queueing: from each request's due
time to the dispatch of the batch that carried it (host clock)."""
from harness.layers import p95


def read(run):
    return p95(r["dispatch"] - r["due"] for r in run.win.rec.values()
               if "dispatch" in r)
