"""What every cell shares: finding a cell, its configuration, its driver and
the per-layer metric readers by name, the device check, the compile cache,
the traced window, and the result line.

A cell is ``bench/workloads/<name>.json``; it names a configuration
(``bench/configs/<config>.json``) and a driver kind (``bench/drivers/<kind>.py``).
Per-layer metrics are ``bench/metrics/<metric>.py``, each with a
``read(ctx)`` that returns a number or ``None`` where the cell has nothing
for it to read. Adding a cell, a configuration or a metric adds a file.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

#: the traced run keeps its profile here, inside the checkout
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
#: JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is unset
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips a cell asks for."""


# ------------------------------------------------------------- lookups
def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, workloads_dir: Optional[str] = None) -> dict:
    path = os.path.join(workloads_dir or os.path.join(BENCH, "workloads"),
                        f"{name}.json")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no cell {name!r} ({path})")
    cell = _load_json(path)
    if cell.get("name") != name:
        raise ValueError(f"{path} names itself {cell.get('name')!r}")
    return cell


def load_config(name: str, configs_dir: Optional[str] = None) -> dict:
    path = os.path.join(configs_dir or os.path.join(BENCH, "configs"),
                        f"{name}.json")
    cfg = _load_json(path)
    if cfg.get("name") != name:
        raise ValueError(f"{path} names itself {cfg.get('name')!r}")
    return cfg


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_driver(kind: str):
    path = os.path.join(BENCH, "drivers", f"{kind}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no driver {kind!r} ({path})")
    return _load_module(path, f"bench_driver_{kind}")


def metric_readers() -> Dict[str, Callable]:
    """Every per-layer metric reader, by metric name (the file's name)."""
    out = {}
    mdir = os.path.join(BENCH, "metrics")
    for fname in sorted(os.listdir(mdir)):
        if fname.endswith(".py") and not fname.startswith("_"):
            name = fname[:-3]
            out[name] = _load_module(os.path.join(mdir, fname),
                                     f"bench_metric_{name}").read
    return out


def scenario_spec(config: dict):
    """The program's ``ScenarioSpec`` for a configuration file."""
    from repro.scenarios.registry import ScenarioSpec

    sc = dict(config["scenario"])
    tup = lambda v: tuple(v) if v is not None else None
    return ScenarioSpec(
        name=config["name"],
        modality=sc["modality"],
        generator=sc["generator"],
        overlap=int(sc["overlap"]),
        num_samples=int(sc["num_samples"]),
        num_parties=int(sc.get("num_parties", 2)),
        gen_params=tuple(sorted(sc.get("gen_params", {}).items())),
        feature_sizes=tup(sc.get("feature_sizes")),
        rep_dim=int(sc["rep_dim"]),
        hidden=tuple(sc.get("hidden", (64,))),
        widths=tuple(sc.get("widths", (8, 16))),
        blocks_per_stage=int(sc.get("blocks_per_stage", 1)),
        ssl_params=tuple(sorted(sc.get("ssl_params", {}).items())),
        description=config.get("deployment", ""))


def extractor_kind(config: dict) -> dict:
    """The reference extractor a configuration's parties run: ``kind``
    (``cnn`` or ``mlp``), CNN stage ``widths`` and ``blocks`` per stage."""
    sc = config["scenario"]
    if sc["modality"] == "image":
        return dict(kind="cnn", widths=tuple(sc["widths"]),
                    blocks=int(sc["blocks_per_stage"]))
    return dict(kind="mlp", widths=(), blocks=1)


def row_gap(got, want) -> float:
    """The worst row's gap over the typical row's size: max_i |got_i - want_i|
    over the root mean square of |want_i|. One altered row shows in full;
    a shape that differs is an infinite gap."""
    import jax.numpy as jnp

    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    if got.shape != want.shape:
        return float("inf")
    got, want = got.reshape(got.shape[0], -1), want.reshape(want.shape[0], -1)
    worst = jnp.max(jnp.linalg.norm(got - want, axis=1))
    typical = jnp.sqrt(jnp.mean(jnp.sum(want * want, axis=1)))
    return float(worst / jnp.maximum(typical, 1e-30))


def derive_seed(seed: int, stream: int) -> int:
    """A 31-bit seed for ``stream`` of run seed ``seed`` (any whole number):
    distinct streams of one seed, and one seed always giving the same."""
    import hashlib

    h = hashlib.sha256(f"{int(seed)}/{int(stream)}".encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x3FFFFFFF


# -------------------------------------------------------------- device
def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> None:
    """Refuse to measure anywhere but on ``chips`` accelerators."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips; JAX found {len(devs)}")


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where it is set
    (JAX reads it itself), else a fixed directory inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# -------------------------------------------------------------- window
@dataclass
class Window:
    """What a driver's window hands back to the harness."""

    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    counters: Dict[str, Any] = field(default_factory=dict)


class Tracer:
    """Starts and stops the profiler around the part of the window the
    traced run records; a no-op in untraced runs."""

    def __init__(self, on: bool) -> None:
        self.on = on
        self.active = False

    def start(self) -> None:
        if self.on and not self.active:
            import jax

            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)
            self.active = True

    def stop(self) -> None:
        if self.active:
            import jax

            jax.profiler.stop_trace()
            self.active = False


def annotate(name: str):
    """A host span the trace reduction reads (``bench.*``)."""
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


def per_layer(ctx: dict) -> Dict[str, Any]:
    """Every reader that finds something to read in this cell."""
    out = {}
    for name, read in metric_readers().items():
        value = read(ctx)
        if value is None:
            continue
        value, unit = value
        if not math.isfinite(value):
            raise ValueError(f"metric {name} read {value}")
        out[name] = {"value": value, "unit": unit}
    return out


def result_line(correct: bool, win: Window, metrics: dict, device: dict,
                checks: dict, breakdown: Optional[dict] = None,
                readings: Optional[dict] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(win.attempted),
           "failed": int(win.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if readings:
        out["readings"] = readings
    out["checks"] = checks
    return json.dumps(out)


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> tuple:
    """Each limit beside its number; correct when every limited number is
    there, finite and at most its limit. Numbers without a limit are
    readings and decide nothing."""
    checks, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and math.isfinite(value) and value <= limit
        ok = ok and good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
