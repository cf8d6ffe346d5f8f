"""Profiler trace (``.xplane.pb``) -> device events, busy intervals and host
annotations: the one reduction every per-layer metric reads.

Device events come from the accelerator planes (``/device:TPU:<n>``): the
``XLA Ops`` line holds one event per operation executed, the ``XLA Modules``
line one per program execution. On the CPU backend (tests) operations run on
host threads; they are the events that carry an ``hlo_op`` stat, and a
program execution is the span of the operations that share one ``run_id``.

Host annotations are the ``bench.*`` spans the harness wraps around each
protocol call or request (``jax.profiler.TraceAnnotation``); the events on the
same host thread name what the host was doing in a device idle gap.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ANNOTATION_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_ID_SUFFIX = re.compile(r"\(\d+\)$")


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    program: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    programs: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)
    annotations: List[Event] = field(default_factory=list)

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    def window(self) -> Tuple[float, float]:
        """From the first harness annotation's start to the last one's end."""
        if not self.annotations:
            raise ValueError("trace holds no bench.* annotation")
        return (min(a.start_ns for a in self.annotations),
                max(a.end_ns for a in self.annotations))


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def load(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` file (or the newest under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    tr = Trace()
    cpu_runs: Dict[Tuple[str, int, int], List[float]] = {}
    host_lines: List[List[Event]] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = f"{m.group(1).lower()}:{m.group(2)}"
            for line in plane.lines:
                if line.name == "XLA Modules":
                    tr.programs[dev] = sorted(
                        (Event(_ID_SUFFIX.sub("", e.name), e.start_ns,
                               e.duration_ns) for e in line.events),
                        key=lambda e: e.start_ns)
                elif line.name == "XLA Ops":
                    tr.ops[dev] = sorted(
                        (Event(e.name, e.start_ns, e.duration_ns)
                         for e in line.events), key=lambda e: e.start_ns)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = []
            for e in line.events:
                name = e.name
                if name.startswith(ANNOTATION_PREFIX):
                    ann = Event(name, e.start_ns, e.duration_ns)
                    tr.annotations.append(ann)
                    evs.append(ann)
                    continue
                st = _stats(e) if plane.name == "/host:CPU" else {}
                if "hlo_op" in st:           # CPU backend: an executed op
                    dev = f"cpu:{st.get('device_ordinal', 0)}"
                    prog = str(st.get("hlo_module", ""))
                    tr.ops.setdefault(dev, []).append(
                        Event(name, e.start_ns, e.duration_ns, prog))
                    span = cpu_runs.setdefault(
                        (dev, prog, int(st.get("run_id", 0))),
                        [e.start_ns, e.start_ns + e.duration_ns])
                    span[0] = min(span[0], e.start_ns)
                    span[1] = max(span[1], e.start_ns + e.duration_ns)
                    continue
                evs.append(Event(name, e.start_ns, e.duration_ns))
            host_lines.append(evs)
    for (dev, prog, _), (s, e) in cpu_runs.items():
        tr.programs.setdefault(dev, []).append(Event(prog, s, e - s))
    for dev in tr.programs:
        tr.programs[dev].sort(key=lambda e: e.start_ns)
    for dev in tr.ops:
        tr.ops[dev].sort(key=lambda e: e.start_ns)
        progs = tr.programs.get(dev, [])
        if progs and not any(e.program for e in tr.ops[dev][:1]):
            tr.ops[dev] = _attach_programs(tr.ops[dev], progs)
    # the host thread that carries the harness annotations
    tr.host = max(host_lines,
                  key=lambda evs: sum(e.name.startswith(ANNOTATION_PREFIX)
                                      for e in evs),
                  default=[])
    tr.host = sorted(tr.host, key=lambda e: e.start_ns)
    tr.annotations.sort(key=lambda e: e.start_ns)
    return tr


def _attach_programs(ops: List[Event], progs: List[Event]) -> List[Event]:
    """Name each operation by the program execution that contains it."""
    starts = [p.start_ns for p in progs]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        prog = progs[i].name if i >= 0 and op.start_ns < progs[i].end_ns else ""
        out.append(Event(op.name, op.start_ns, op.dur_ns, prog))
    return out


# ------------------------------------------------------------ intervals
def union(events: Iterable[Event], start: float, end: float
          ) -> List[Tuple[float, float]]:
    """Merged busy intervals of ``events``, clipped to [start, end]."""
    spans = sorted((max(e.start_ns, start), min(e.end_ns, end))
                   for e in events if e.end_ns > start and e.start_ns < end)
    merged: List[List[float]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(events: Iterable[Event], start: float, end: float) -> float:
    return sum(e - s for s, e in union(events, start, end))


def idle_gaps(events: Iterable[Event], start: float, end: float
              ) -> List[Tuple[float, float]]:
    """(start, length) of every stretch of [start, end] with no event."""
    gaps, cursor = [], start
    for s, e in union(events, start, end):
        if s > cursor:
            gaps.append((cursor, s - cursor))
        cursor = max(cursor, e)
    if end > cursor:
        gaps.append((cursor, end - cursor))
    return gaps


def host_activity(tr: Trace, t: float) -> str:
    """What the host was doing at ``t``: the innermost harness annotation
    and the innermost other event on its thread that contain ``t``."""
    ann = [a for a in tr.annotations if a.start_ns <= t < a.end_ns]
    inner = [e for e in tr.host if e.start_ns <= t < e.end_ns
             and not e.name.startswith(ANNOTATION_PREFIX)]
    parts = []
    if ann:
        parts.append(min(ann, key=lambda a: a.dur_ns).name)
    if inner:
        parts.append(min(inner, key=lambda e: e.dur_ns).name)
    return "/".join(parts) or "no host span"


def op_name(name: str) -> str:
    """An operation's own name: a TPU event is named by its HLO instruction
    (``%name = type op(operands)``), whose operands name other ops."""
    return name.split(" = ", 1)[0].lstrip("%")


def kernel_events(tr: Trace, dev: str, patterns: Sequence[str]
                  ) -> List[Event]:
    """Operations whose own name contains any of ``patterns``."""
    return [e for e in tr.ops.get(dev, [])
            if any(p in op_name(e.name) for p in patterns)]


def program_events(tr: Trace, dev: str, patterns: Sequence[str]
                   ) -> List[Event]:
    return [p for p in tr.programs.get(dev, [])
            if any(s in p.name for s in patterns)]


def in_window(events: Iterable[Event], start: float, end: float
              ) -> List[Event]:
    return [e for e in events if e.start_ns >= start and e.end_ns <= end]


def breakdown(tr: Trace, devices: Optional[Sequence[str]] = None,
              top: int = 10) -> dict:
    """The device programs that took most time and the longest idle gaps,
    each named by what the host was doing, over the traced window."""
    start, end = tr.window()
    devs = list(devices or tr.devices)
    by_prog: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for dev in devs:
        for p in in_window(tr.programs.get(dev, []), start, end):
            by_prog[p.name] = by_prog.get(p.name, 0.0) + p.dur_ns * 1e-9
        gaps.extend(idle_gaps(tr.ops.get(dev, []), start, end))
    ops = sorted(by_prog.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(gaps, key=lambda g: -g[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[host_activity(tr, s + d / 2), d * 1e-9]
                          for s, d in longest]}
