"""kmeans_roofline: the Pallas k-means assignment kernel's events in the
traced calls against its operations and bytes (bench/flops.py) and the
chip's peaks. Moves protocol_s."""
from bench.metrics import _common

#: the kernel's HLO instruction name: its pallas_call's enclosing function
#: in kernels/kmeans/kernel.py
PATTERNS = ("kmeans_assign_batched_padded",)


def read(ctx):
    return _common.roofline(ctx, "kmeans", PATTERNS)
