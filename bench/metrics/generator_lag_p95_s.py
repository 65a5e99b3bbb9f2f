"""95th percentile of how late the load generator submitted each
request of an open loop: submit time minus due time (host clock)."""
from harness.layers import p95


def read(run):
    if run.win.due is None:
        return None
    return p95(r["submit"] - r["due"] for r in run.win.rec.values())
