"""What the phase-span readers share: the program's own ``vfl.*`` spans
(``src/repro/obs.py``) inside the traced protocol calls, the device idle time
inside them, and the metadata the profiler keeps on ``vfl.run``.

Device idle inside a span is the span's interval minus the union of the
device's operations in it. The phase spans of a call follow one another, so
the idle inside all of them plus the idle that no phase span covers is the
idle of the calls. A trace of a program without these spans gives nothing to
read, and the readers return ``None``.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

from bench import harness
from bench import trace_reduce as tr_mod
from bench.metrics import _common

PREFIX = "vfl."
#: the phase spans of one protocol call: one-shot ①-⑥ and few-shot ①'-⑥'
PHASES = (
    "init",
    "p1.extract",
    "p2.grads",
    "p3.kmeans",
    "p4.ssl",
    "p5.extract",
    "p6.fit",
    "f1.extract",
    "f2.aux",
    "f3.sdpa",
    "f4.probs",
    "f5.ssl",
    "f6.fit",
    "eval",
)


def program_spans(ctx, names: Sequence[str]) -> List[tr_mod.Event]:
    """The ``vfl.<name>`` spans on the annotated host thread that start
    inside a traced call."""
    want = {PREFIX + n for n in names}
    calls = _common.spans(ctx, "call")
    return _common.inside([e for e in ctx["trace"].host if e.name in want], calls)


def busy_intervals(ctx, dev: str) -> List[Tuple[float, float]]:
    """The device's merged busy intervals over the traced calls, merged once
    per run and kept in ``ctx`` for the other readers."""
    kept = ctx.setdefault("_vfl_busy", {})
    if dev not in kept:
        calls = _common.spans(ctx, "call")
        start, end = min(c.start_ns for c in calls), max(c.end_ns for c in calls)
        kept[dev] = tr_mod.union(ctx["trace"].ops.get(dev, []), start, end)
    return kept[dev]


def idle_ns(busy: Sequence[Tuple[float, float]], windows: Sequence[tr_mod.Event]) -> float:
    """Idle inside each of ``windows``, summed, given merged ``busy``
    intervals in order."""
    ends = [e for _, e in busy]
    total = 0.0
    for w in windows:
        covered = 0.0
        i = bisect.bisect_right(ends, w.start_ns)
        while i < len(busy) and busy[i][0] < w.end_ns:
            covered += min(busy[i][1], w.end_ns) - max(busy[i][0], w.start_ns)
            i += 1
        total += w.dur_ns - covered
    return total


def uncovered(calls: Sequence[tr_mod.Event], covers: Sequence[tr_mod.Event]) -> List[tr_mod.Event]:
    """The stretches of ``calls`` that none of ``covers`` covers."""
    out = []
    for c in calls:
        for start, length in tr_mod.idle_gaps(covers, c.start_ns, c.end_ns):
            out.append(tr_mod.Event("uncovered", start, length))
    return out


def per_call_ms(ctx, windows: Sequence[tr_mod.Event]) -> Optional[float]:
    """Device idle inside ``windows``, averaged over the cell's chips, per
    traced call, in ms."""
    calls = _common.spans(ctx, "call")
    if not calls:
        return None
    idle = _common.per_device(ctx, lambda d: idle_ns(busy_intervals(ctx, d), windows))
    return sum(idle) / len(idle) / len(calls) * 1e-6


def idle_ms(ctx, names: Sequence[str]) -> Optional[Tuple[float, str]]:
    """Device idle per call inside the ``names`` phase spans, in ms; ``None``
    where the traced calls hold no such span."""
    if ctx["counters"].get("kind") != "protocol":
        return None
    spans = program_spans(ctx, names)
    if not spans:
        return None
    return per_call_ms(ctx, spans), "ms"


def unspanned_ms(ctx) -> Optional[Tuple[float, str]]:
    """Device idle per call inside the traced calls that no phase span
    covers, in ms; ``None`` where the calls hold no phase span."""
    if ctx["counters"].get("kind") != "protocol":
        return None
    spans = program_spans(ctx, PHASES)
    if not spans:
        return None
    return per_call_ms(ctx, uncovered(_common.spans(ctx, "call"), spans)), "ms"


# ------------------------------------------------------- vfl.run metadata
def run_metadata(directory: str) -> List[Tuple[tr_mod.Event, Dict]]:
    """Every ``vfl.run`` event of the newest profile under ``directory``,
    with the stats the program attached to it (``repro.obs``). The trace
    reduction keeps no stats, so the file is read again, host planes only."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(tr_mod.find_xplane(directory))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == PREFIX + "run":
                    ev = tr_mod.Event(e.name, e.start_ns, e.duration_ns)
                    out.append((ev, dict(e.stats)))
    return out


def compiles(
    calls: Sequence[tr_mod.Event], runs: Sequence[Tuple[tr_mod.Event, Dict]]
) -> Optional[float]:
    """Backend compilations (persistent-cache loads included) in the
    ``vfl.run`` spans of the traced calls; ``None`` where no such span
    carries the count."""
    inside = [stats for ev, stats in runs if _common.inside([ev], calls)]
    counts = [stats["compiles"] for stats in inside if "compiles" in stats]
    return float(sum(counts)) if counts else None


def compiles_in_window(ctx) -> Optional[Tuple[float, str]]:
    if ctx["counters"].get("kind") != "protocol":
        return None
    calls = _common.spans(ctx, "call")
    if not calls:
        return None
    try:
        runs = run_metadata(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    n = compiles(calls, runs)
    return None if n is None else (n, "count")
