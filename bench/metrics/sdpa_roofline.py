"""sdpa_roofline: the Pallas Eq. 10 kernel's events in the traced calls
(few-shot step 3') against its operations and bytes (bench/flops.py::sdpa)
and the chip's peaks. Moves protocol_s."""
from bench.metrics import _common

#: the kernel's HLO instruction name: its pallas_call's enclosing function
#: in kernels/sdpa_estimator/kernel.py
PATTERNS = ("sdpa_estimate_batched_padded",)


def read(ctx):
    return _common.roofline(ctx, "sdpa", PATTERNS)
