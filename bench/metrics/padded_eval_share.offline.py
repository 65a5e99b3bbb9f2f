"""UNet evaluations spent on padding steps over UNet evaluations run, in
%: evaluations run per program execution are counted from its
flash-attention events in the trace; useful ones are the request's
steps times its guidance branches."""
from harness import layers


def read(run):
    pm = layers.per_module(run)
    if pm is None:
        return None
    useful = run.mix["request"]["steps"] * layers.branches(run)
    return 100.0 * (1.0 - useful / pm["unet_evals"])
