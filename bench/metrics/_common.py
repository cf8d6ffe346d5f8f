"""Helpers the per-layer metric readers share: the traced protocol calls or
requests, and device time inside them."""
from __future__ import annotations

from typing import List, Optional, Tuple

from bench import trace_reduce as tr_mod


def spans(ctx, prefix: str) -> List[tr_mod.Event]:
    """The harness annotations whose name starts with ``bench.<prefix>``."""
    return [a for a in ctx["trace"].annotations
            if a.name.startswith(f"bench.{prefix}")]


def per_device(ctx, fn) -> List[float]:
    return [fn(dev) for dev in ctx["devices"]]


def inside(events, windows: List[tr_mod.Event]) -> list:
    """Events that start inside any of ``windows``."""
    out = []
    for e in events:
        for w in windows:
            if w.start_ns <= e.start_ns < w.end_ns:
                out.append(e)
                break
    return out


def idle_share(ctx) -> float:
    """1 - device busy time over the traced window, averaged over chips, %."""
    start, end = ctx["trace"].window()
    busy = per_device(ctx, lambda d: tr_mod.busy_ns(ctx["trace"].ops.get(d, []),
                                                    start, end))
    return 100.0 * (1.0 - sum(busy) / len(busy) / (end - start))


def roofline(ctx, kernel: str, patterns) -> Optional[Tuple[float, str]]:
    """Least time the chip could take for ``kernel``'s work in the traced
    calls over the device time its events took, %."""
    calls = spans(ctx, "call")
    work = ctx["counters"].get("kernels", {}).get(kernel)
    if not calls or work is None:
        return None
    ops, nbytes = work
    t = sum(sum(e.dur_ns for e in inside(
        tr_mod.kernel_events(ctx["trace"], d, patterns), calls))
        for d in ctx["devices"]) * 1e-9
    if t <= 0:
        return None
    peak = ctx["peak"]
    least = len(calls) * max(ops / peak["flops_per_s"],
                             nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / t, "%"
