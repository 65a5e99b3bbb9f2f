"""Share of the roofline reached by the q3k_matmul kernel, in %: the least
time of its calls at the chip's peaks (the larger of operations over
bf16 FLOP/s and least bytes over HBM bandwidth, per call) over the
device time of its events in the trace."""
from harness import layers


def read(run):
    return layers.kernel_roofline(run, "q3k_matmul")
