"""Host spans and process counters of the VFL program: its one tracing system.

``span(name, **attrs)`` marks a stretch of host code as ``vfl.<name>``. While
the JAX profiler traces, the span is a profiler annotation
(``jax.profiler.TraceAnnotation``), so it lands on the clock of the device
planes and every device idle gap can be put down to the phase the host was
in. On exit it carries, as event metadata, the counters that moved inside
it and the ``run`` id that every span of one protocol call shares; ``vfl.run``
carries every counter's delta, moved or not. While the profiler is off a
span costs one ``TraceMe.is_enabled()`` check and enters a shared null
context.

The span tree of a protocol call::

    vfl.run                          core/protocol.py  run_scenarios_seeds
      vfl.init                         clients and servers
      vfl.p1.extract .. vfl.p6.fit     one-shot phases ①-⑥ (_one_shot_seeds)
        vfl.ssl.group                  engine/local_ssl.py, inside p4.ssl (and
          vfl.ssl.schedule               f5.ssl): one per stacked session
          vfl.ssl.session                (members, steps, rows), around its
          vfl.ssl.readback               schedules, dispatch and outputs
      vfl.eval                         test evaluation
      vfl.f1.extract .. vfl.f6.fit     few-shot phases ①'-⑥' (_few_shot_seeds,
      vfl.eval                           after its one-shot pass)

The phase spans (``init``, ``p1``-``p6``, ``f1``-``f6``, ``eval``) follow one
another and never overlap.

``counters()`` gives process-wide integers for an operator who runs without
the profiler: ``compiles`` (every backend compilation, a program loaded from
the persistent compilation cache included), ``persistent_cache_hits`` (the
loads among them), ``ssl_host_steps`` (SSL steps the per-party host loop
dispatched one by one; 0 while every session runs scanned),
``ssl_schedule_draws`` (host generator calls the SSL schedule builds made:
2 per entry and epoch, 1 with an empty unlabeled pool) and the
compiled-session cache's hits and misses (``engine.sessions``).

Rules:

* A span never blocks and never reads a device value: it reads host
  integers only.
* No span goes inside a per-step or per-batch loop.
* A span's name is stable API: the benchmark reads it.
"""

from __future__ import annotations

import contextlib
import itertools
from typing import Dict

import jax
from jax._src.lib import _profiler

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_COUNTS = {
    "compiles": 0,
    "persistent_cache_hits": 0,
    "ssl_host_steps": 0,
    "ssl_schedule_draws": 0,
}
_NULL = contextlib.nullcontext()
_RUN_IDS = itertools.count(1)
_run = 0  # id of the protocol call in progress (its ``vfl.run``)


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == _BACKEND_COMPILE:
        _COUNTS["compiles"] += 1


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT:
        _COUNTS["persistent_cache_hits"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def count(name: str, n: int) -> None:
    """Add ``n`` to the process counter ``name``."""
    _COUNTS[name] += n


def counters() -> Dict[str, int]:
    """The process's counters since start-up."""
    from repro.engine import sessions  # deferred: the engine imports this

    st = sessions.session_cache_stats()
    return {**_COUNTS, "session_hits": st["hits"], "session_misses": st["misses"]}


class _Span:
    def __init__(self, name: str, attrs: dict) -> None:
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        global _run
        if self.name == "run":
            _run = next(_RUN_IDS)
        self.before = counters()
        self.ann = jax.profiler.TraceAnnotation(f"vfl.{self.name}")
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        after = counters()
        keep_all = self.name == "run"
        moved = {k: after[k] - v for k, v in self.before.items() if keep_all or after[k] != v}
        self.ann.set_metadata(run=_run, **self.attrs, **moved)
        return self.ann.__exit__(*exc)


def span(name: str, **attrs):
    """A ``vfl.<name>`` profiler annotation while the profiler traces, else
    a shared null context. ``attrs`` are host ints or strings."""
    if not _profiler.TraceMe.is_enabled():
        return _NULL
    return _Span(name, attrs)
