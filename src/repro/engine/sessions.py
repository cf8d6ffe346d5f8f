"""The shared compiled-session cache (DESIGN.md §9).

Every whole-session jitted program in the repo — the iterative baselines'
``lax.scan`` sessions (``engine.iterative``), the one-shot/few-shot local-SSL
sessions (``engine.local_ssl``), the server classifier fits
(``core.server._fit``), and the party extractors' forward behind every
rep extraction and evaluation (``core.client.extract_program``) — is built
once per *semantic* step identity and
re-served from here on every later call. Training data always travels as
arguments, never inside the cached closure, so one compiled program serves
every seed and every scenario point of equal shapes; ``jax.jit``'s own
shape-specialization handles the rest.

Cache keys combine:

* ``model_key(model)`` — the semantic identity of a ``Model``: the apply
  function's code object plus its captured closure values (the guarantee
  ``local_ssl._apply_fns_match`` relies on). Two
  ``make_mlp_extractor(rep_dim=16, hidden=(32,))`` calls return distinct
  closures with equal keys, so sessions built for one re-serve the other.
* hashable hyper-parameter records (frozen dataclasses like ``SSLHParams``
  / ``IterHParams`` / ``SSLConfig``, plain floats/ints/bools).

Hit/miss counters are tracked per *domain* (the first element of every
cache key: ``"iterative"``, ``"ssl"``, ``"server_fit"``, ``"kmeans"``,
``"extract"``) so
benchmarks can report compile counts per subsystem and tests can pin the
no-recompile contract without cross-talk
(``session_cache_stats(domain=...)``).

Because keys never encode batch width, the seed-batched folds of
DESIGN.md §10 (``engine.batched``) re-serve the same cached programs at
any stacked S·K shape — multi-seed sweeps add zero fresh session builds
beyond the first seed.
"""
from __future__ import annotations

import hashlib
from typing import Any, Callable, Dict, Optional

import numpy as np

from repro.models.extractors import Model

_SESSION_CACHE: Dict[tuple, Any] = {}
_CACHE_STATS: Dict[str, Dict[str, int]] = {}


class _Undigested:
    """A closure cell ``model_key`` could not digest: a fresh instance
    equals no other, so a key holding one never hits."""


def _domain_stats(domain: str) -> Dict[str, int]:
    return _CACHE_STATS.setdefault(domain, {"hits": 0, "misses": 0})


def session_cache_stats(domain: Optional[str] = None) -> Dict[str, int]:
    """Aggregate ``{"hits": .., "misses": ..}``; pass ``domain`` to restrict
    to one subsystem ("iterative" | "ssl" | "server_fit" | "extract" | …)."""
    if domain is not None:
        return dict(_domain_stats(domain))
    out = {"hits": 0, "misses": 0}
    for st in _CACHE_STATS.values():
        out["hits"] += st["hits"]
        out["misses"] += st["misses"]
    return out


def session_cache_stats_by_domain() -> Dict[str, Dict[str, int]]:
    """Per-domain hit/miss counters (what ``benchmarks/frontier.py``
    serializes into ``BENCH_frontier.json``)."""
    return {d: dict(st) for d, st in sorted(_CACHE_STATS.items())}


def clear_session_cache() -> None:
    _SESSION_CACHE.clear()
    _CACHE_STATS.clear()


def model_key(m: Model) -> tuple:
    """Semantic identity of a Model: apply-fn code + captured closure values.

    Parameters travel as arguments, never in the closure, so equal code +
    equal closure cells ⇒ the same pure forward function."""
    fn = m.apply
    cells = []
    for c in (fn.__closure__ or ()):
        v = c.cell_contents
        try:
            hash(v)
            cells.append(v)
        except TypeError:
            try:
                # arrays: digest the full contents — repr() truncates large
                # arrays, which could alias two different constants onto one
                # cache key and silently re-serve the wrong program
                arr = np.asarray(v)
                if arr.dtype == object:
                    raise TypeError("not a numeric array")
                cells.append(("arr", arr.shape, str(arr.dtype),
                              hashlib.sha1(arr.tobytes()).hexdigest()))
            except Exception:
                # un-digestable cell (dict/object closures): a fresh token
                # guarantees a cache MISS — recompiling is safe, re-serving
                # another model's program is not (and repr()/pointer bytes
                # can collide across gc'd addresses)
                cells.append(_Undigested())
    return (getattr(fn, "__code__", None), tuple(cells), m.rep_dim)


def is_digested(key: tuple) -> bool:
    """Whether a ``model_key`` result digested every closure cell, so that
    an equal model built later gives an equal key."""
    return not any(isinstance(c, _Undigested) for c in key[1])


def cached_session(domain: str, key: tuple, builder: Callable[[], Any]) -> Any:
    """Return the compiled callable cached under ``(domain,) + key``,
    building (and counting a miss for ``domain``) on first use."""
    full = (domain,) + key
    fn = _SESSION_CACHE.get(full)
    stats = _domain_stats(domain)
    if fn is None:
        stats["misses"] += 1
        fn = builder()
        _SESSION_CACHE[full] = fn
    else:
        stats["hits"] += 1
    return fn
