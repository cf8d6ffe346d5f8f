"""device_idle.train: share of the traced protocol calls in which no
operation ran on the device, averaged over the cell's chips. Moves
protocol_s."""
from bench.metrics import _common


def read(ctx):
    if ctx["counters"].get("kind") != "protocol":
        return None
    return _common.idle_share(ctx), "%"
