"""Bring-up check: one-shot / few-shot VFL, its Pallas kernels and serving on
a TPU, through the entry points a user calls.

    python3 chip_smoke.py             # one chip: kernels, training, serving
    python3 chip_smoke.py --chips 4   # four chips: the sharded fold only

One chip, in order:

1. device — the default device must be a TPU and the Pallas kernels must
   compile (``repro.kernels.interpret_mode()`` is False); otherwise exit 2
   before any work.
2. kernels — both batched Pallas grids against their jnp oracles and a
   float64 host reference (the precision probe behind the parity bounds).
3. train — the stacked ``hard/overlap-{32,64}-eq`` group at its registered
   size (3000 rows, K=2), seeds 0 and 1, one-shot and few-shot through
   ``run_scenarios_seeds`` exactly as ``benchmarks/frontier.py`` calls it,
   on the kernel route and again on the jnp route.
4. serve — one trained result exported with ``save_artifact``, reloaded,
   and served through ``ServingEngine`` (capacity 64), including one
   partial-party query.

``--chips 4`` runs the same group with its stacked S·C·K axis sharded over
a 4-device mesh against the unsharded fold, plus one scenario at S=3 (6
entries, padded to 8).

Every phase runs twice where it is timed: the first call includes
compilation, the second does not. The wall times printed are from one smoke
run, not a benchmark. Any failed check exits 1; the last line of standard
output is a JSON object with ``"ok": true`` only when every check passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.frontier import runner_cfgs
from repro import scenarios
from repro.checkpoint import load_artifact, save_artifact
from repro.core.protocol import run_scenarios_seeds
from repro.engine import resolve_mesh, session_cache_stats
from repro.engine.dispatch import estimate_missing_fused
from repro.kernels import interpret_mode
from repro.kernels.kmeans import ops as km_ops
from repro.kernels.kmeans import ref as km_ref
from repro.kernels.sdpa_estimator import ops as sdpa_ops
from repro.kernels.sdpa_estimator import ref as sdpa_ref
from repro.launch import vfl_serve
from repro.launch.compile_cache import enable_compile_cache

OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

GROUP = ("hard/overlap-32-eq", "hard/overlap-64-eq")
SEEDS = (0, 1)
METHODS = ("one_shot", "few_shot")
COMM_TIMES = {"one_shot": 3, "few_shot": 5}
SERVE_CAPACITY = 64
#: kernel vs jnp oracle on the same device. k-means assignments must agree
#: exactly; SDPA estimates and everything downstream of them within PARITY.
PARITY = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def timed_twice(fn):
    """Run ``fn`` twice; returns (second result, first-call s, second s)."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t2 = time.perf_counter()
    return out, t1 - t0, t2 - t1


def log_times(phase: str, first: float, second: float) -> None:
    log(f"[time] {phase}: first call (compile) {first:.3f} s, "
        f"second call {second:.3f} s  (one smoke run)")


def _ledger_events(ledger) -> list:
    return [(e.party, e.direction, e.tag, e.bytes, e.round)
            for e in ledger.events]


# ------------------------------------------------------------- kernels --
def kernel_phase(problems: list) -> None:
    """Both batched kernels vs their jnp oracles on this device, each also
    against a float64 host reference — which side a disagreement comes from
    is what decides the parity bounds."""
    kx, kc, ku, ka, kb = jax.random.split(jax.random.PRNGKey(0), 5)

    def unit_rows(key, shape):
        v = jax.random.normal(key, shape)
        return v / jnp.linalg.norm(v, axis=-1, keepdims=True)

    # step ③ on cosine-normalized gradient rows: stacked fold of 8, C=10
    x = unit_rows(kx, (8, 4096, 16))
    cen = unit_rows(kc, (8, 10, 16))
    got, t1, t2 = timed_twice(lambda: km_ops.kmeans_assign_batched(x, cen))
    log_times("kernels/kmeans_assign_batched (8, 4096, 16) C=10", t1, t2)
    want = jax.vmap(km_ref.kmeans_assign)(x, cen)
    x64, c64 = np.asarray(x, np.float64), np.asarray(cen, np.float64)
    d64 = (np.sum(x64 ** 2, -1)[..., None]
           - 2 * np.einsum("bnd,bcd->bnc", x64, c64)
           + np.sum(c64 ** 2, -1)[:, None, :])
    exact = np.argmin(d64, -1)
    got, want = np.asarray(got), np.asarray(want)
    flips = int(np.sum(got != want))
    log(f"[kernels] kmeans: kernel vs jnp oracle {flips} flips; vs float64 "
        f"kernel {int(np.sum(got != exact))}, oracle "
        f"{int(np.sum(want != exact))} (of {got.size})")
    if flips:
        problems.append(f"kmeans kernel and jnp oracle disagree on {flips} "
                        f"assignments")

    # few-shot ③' at the smoke run's fold: S·C = 4, one party's private pool
    hu = jax.random.normal(ku, (4, 1168, 16))
    hoa = jax.random.normal(ka, (4, 64, 16))
    hob = jax.random.normal(kb, (4, 64, 16))
    got, t1, t2 = timed_twice(
        lambda: sdpa_ops.sdpa_estimate_batched(hu, hoa, hob))
    log_times("kernels/sdpa_estimate_batched (4, 1168, 64, 16)", t1, t2)
    want = jax.vmap(sdpa_ref.sdpa_estimate)(hu, hoa, hob)
    u64, a64, b64 = (np.asarray(v, np.float64) for v in (hu, hoa, hob))
    s64 = np.einsum("bud,bod->buo", u64, a64) / np.sqrt(16.0)
    p64 = np.exp(s64 - s64.max(-1, keepdims=True))
    exact = np.einsum("buo,bod->bud", p64 / p64.sum(-1, keepdims=True), b64)
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = float(np.max(np.abs(got - want)))
    log(f"[kernels] sdpa: |kernel - jnp oracle| max {err:.3e}; vs float64 "
        f"kernel {np.max(np.abs(got - exact)):.3e}, oracle "
        f"{np.max(np.abs(want - exact)):.3e}")
    if not err <= PARITY:
        problems.append(f"sdpa kernel vs jnp oracle {err:.3e} > {PARITY}")


# --------------------------------------------------------------- train --
def build_group(seeds=SEEDS, names=GROUP):
    """The C×S grid of built bundles, asserted to stack as one group."""
    bundles = [[scenarios.build(scenarios.get(n), seed=s, smoke=False)
                for s in seeds] for n in names]
    groups = scenarios.group_scenarios([(bs[0].spec, bs[0])
                                        for bs in bundles])
    if len(groups) != 1:
        raise RuntimeError(f"{names} did not stack into one group: "
                           f"{[g.names for g in groups]}")
    return bundles


def run_group(bundles, seeds, method, use_kernels, devices=None,
              budgets=None):
    """One folded sweep of ``method`` over the group, exactly as
    ``benchmarks.frontier.run_scenario_group`` calls it. ``budgets``
    (field → value) overrides the scenario's training budget."""
    specs = [bs[0].spec for bs in bundles]
    runner, cfg = runner_cfgs(specs[0], (method,), devices=devices,
                              use_kernels=use_kernels)[method]
    if budgets:
        cfg = dataclasses.replace(cfg, **budgets)
    return run_scenarios_seeds(
        runner,
        [[jax.random.PRNGKey(s) for s in seeds] for _ in specs],
        [[b.split for b in bs] for bs in bundles],
        [[b.extractors for b in bs] for bs in bundles],
        [[b.ssl_cfgs for b in bs] for bs in bundles],
        cfg), cfg


def _flat(grid):
    return [r for row in grid for r in row]


def train_phase(problems: list, seeds=SEEDS, budgets=None) -> dict:
    """One-shot and few-shot on both routes; returns
    ``{(method, route): (results grid, cfg)}``."""
    bundles = build_group(seeds)
    n_entries = len(seeds) * len(GROUP)
    want_km = n_entries * bundles[0][0].spec.num_parties
    out = {}
    for route, use_kernels in (("kernel", True), ("jnp", False)):
        for method in METHODS:
            t0 = time.perf_counter()
            run_group(bundles, seeds, method, use_kernels, budgets=budgets)
            t1 = time.perf_counter()
            grid, cfg = run_group(bundles, seeds, method, use_kernels,
                                  budgets=budgets)
            t2 = time.perf_counter()
            log_times(f"train/{method}/{route}", t1 - t0, t2 - t1)
            out[(method, route)] = (grid, cfg)
            for r in _flat(grid):
                d = r.diagnostics
                bad = []
                if d.get("kernel_fold") != want_km:
                    bad.append(f"kernel_fold={d.get('kernel_fold')} "
                               f"(want {want_km})")
                if d.get("kernel_fallback"):
                    bad.append(f"kernel_fallback={d['kernel_fallback']!r}")
                if method == "few_shot" and d.get("sdpa_fold") != n_entries:
                    bad.append(f"sdpa_fold={d.get('sdpa_fold')} "
                               f"(want {n_entries})")
                times = {r.ledger.comm_times(k)
                         for k in range(bundles[0][0].spec.num_parties)}
                if times != {COMM_TIMES[method]}:
                    bad.append(f"comm times per client {sorted(times)}")
                if not np.isfinite(r.metric):
                    bad.append(f"metric {r.metric}")
                if bad:
                    problems.append(f"{method}/{route}: " + ", ".join(bad))
            log(f"[train] {method}/{route}: "
                + " ".join(f"{r.metric_name}={r.metric:.6f}"
                           for r in _flat(grid)))

    for method in METHODS:
        kern = _flat(out[(method, "kernel")][0])
        ref = _flat(out[(method, "jnp")][0])
        diff = max(abs(a.metric - b.metric) for a, b in zip(kern, ref))
        flips = sum(int(jnp.sum(pa != pb))
                    for a, b in zip(kern, ref)
                    for pa, pb in zip(a.diagnostics["pseudo_labels"],
                                      b.diagnostics["pseudo_labels"]))
        same_ledgers = all(_ledger_events(a.ledger) == _ledger_events(b.ledger)
                           for a, b in zip(kern, ref))
        log(f"[train] {method}: kernel vs jnp metric max |diff| {diff:.3e}, "
            f"step-3 pseudo-label flips {flips}, ledgers identical "
            f"{same_ledgers}")
        if flips:
            problems.append(f"{method}: kernel and jnp routes disagree on "
                            f"{flips} step-3 pseudo-labels")
        if not diff <= PARITY:
            problems.append(f"{method}: kernel vs jnp metric diff {diff:.3e}"
                            f" > {PARITY}")
        if not same_ledgers:
            problems.append(f"{method}: ledgers differ between routes")
    return out


# --------------------------------------------------------------- serve --
def serve_phase(problems: list, result, spec, cfg, split,
                out_dir: str = OUT_DIR) -> None:
    """Export → save → load → serve a few batches and a partial-party
    query; parity against the artifact's unbatched reference forward."""
    path = os.path.join(out_dir, "artifact")
    shutil.rmtree(path, ignore_errors=True)
    save_artifact(path, result.to_artifact(spec, cfg=cfg, split=split))
    art = load_artifact(path)
    log(f"[serve] artifact {path}: scenario={art.scenario} "
        f"K={art.num_parties} classes={art.num_classes}")

    engine = vfl_serve.ServingEngine(art, capacity=SERVE_CAPACITY)
    reqs = vfl_serve.synthetic_requests(art, 4, SERVE_CAPACITY, seed=1)
    misses0 = session_cache_stats("serving")["misses"]
    outs, t1, t2 = timed_twice(
        lambda: vfl_serve.serve_traffic(engine, reqs)[0])
    log_times(f"serve/fused forward, 4 batches of {SERVE_CAPACITY}", t1, t2)
    # ragged traffic through a second capacity: same cached program
    ragged = vfl_serve.synthetic_requests(art, 3, 37, seed=2)
    small = vfl_serve.ServingEngine(art, capacity=16)
    misses1 = session_cache_stats("serving")["misses"]
    outs_small = [small.predict_logits(list(r)) for r in ragged]
    fresh = session_cache_stats("serving")["misses"] - misses1
    parity = max(
        [float(jnp.max(jnp.abs(o - art.predict_logits(list(r)))))
         for o, r in zip(outs, reqs)]
        + [float(jnp.max(jnp.abs(o - art.predict_logits(list(r)))))
           for o, r in zip(outs_small, ragged)])
    log(f"[serve] batched vs unbatched max |diff| {parity:.3e}; serving "
        f"builds: first shape {misses1 - misses0}, after it {fresh}")
    if not parity <= PARITY:
        problems.append(f"serving parity {parity:.3e} > {PARITY}")
    if fresh != 0:
        problems.append(f"{fresh} fresh serving builds after the first "
                        f"shape")

    # partial-party query: only party 0's features, the others estimated
    # by Eq. 10 over the artifact's overlap reps; big enough that the
    # router takes the Pallas kernel on a TPU
    x0 = vfl_serve.synthetic_requests(art, 1, 16384, seed=3)[0][0]
    routed, t1, t2 = timed_twice(
        lambda: engine.predict_logits_partial(x0, 0))
    n_o, d = art.overlap_reps[0].shape
    on_kernel = engine.router.use_sdpa(16384, int(n_o), int(d),
                                       batch=art.num_parties - 1)
    log_times(f"serve/partial-party 16384 rows "
              f"({'kernel' if on_kernel else 'jnp'} route)", t1, t2)
    if routed.shape != (16384, art.num_classes) \
            or not bool(jnp.all(jnp.isfinite(routed))):
        problems.append(f"partial-party logits malformed {routed.shape}")
    # the route changes only the Eq. 10 estimates, so parity is held there.
    # The joint head after them runs at the device's default matmul
    # precision, which on a TPU rounds its inputs to bf16: an estimate that
    # moves by 1e-6 across a rounding boundary moves a logit by one bf16 ulp
    # of the input, so the logits are compared for information only.
    h_u = art.extractors()[0].apply(art.client_params[0].extractor, x0)
    est = estimate_missing_fused(h_u, art.overlap_reps, 0,
                                 use_kernels=on_kernel)
    ref = estimate_missing_fused(h_u, art.overlap_reps, 0, use_kernels=False)
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(est, ref))
    jnp_engine = vfl_serve.ServingEngine(
        art, capacity=SERVE_CAPACITY,
        router=dataclasses.replace(engine.router, interpret=True))
    logit_err = float(jnp.max(jnp.abs(
        routed - jnp_engine.predict_logits_partial(x0, 0))))
    log(f"[serve] partial-party Eq. 10 estimates routed vs jnp max |diff| "
        f"{err:.3e}; logits {tuple(routed.shape)} routed vs jnp route max "
        f"|diff| {logit_err:.3e} (head at default precision)")
    if not err <= PARITY:
        problems.append(f"partial-party estimates routed vs jnp {err:.3e} "
                        f"> {PARITY}")


# ------------------------------------------------------------- sharded --
def sharded_phase(problems: list, devices: int = 4, budgets=None) -> None:
    """The stacked axis sharded over ``devices`` chips ≡ the unsharded
    fold: the eq group (S·C·K = 8) and one scenario at S=3 (6 entries,
    padded), one-shot and few-shot."""
    mesh = resolve_mesh(devices)
    ids = sorted(d.id for d in mesh.devices.flat)
    log(f"[sharded] mesh {dict(mesh.shape)} over device ids {ids}")
    if len(set(ids)) != devices:
        problems.append(f"mesh spans {len(set(ids))} distinct devices, "
                        f"want {devices}")
    cases = (("group S=2 C=2", GROUP, SEEDS), ("single S=3", GROUP[:1],
                                                (0, 1, 2)))
    for label, names, seeds in cases:
        bundles = build_group(seeds, names)
        for method in METHODS:
            t0 = time.perf_counter()
            single, _ = run_group(bundles, seeds, method, True,
                                  budgets=budgets)
            t1 = time.perf_counter()
            sharded, _ = run_group(bundles, seeds, method, True,
                                   devices=devices, budgets=budgets)
            t2 = time.perf_counter()
            log(f"[time] sharded/{label}/{method}: unsharded {t1 - t0:.3f} "
                f"s, mesh={devices} {t2 - t1:.3f} s, both first calls "
                f"(one smoke run)")
            metric_err = leaf_err = 0.0
            for a, b in zip(_flat(sharded), _flat(single)):
                metric_err = max(metric_err, abs(a.metric - b.metric))
                for ca, cb in zip(a.clients, b.clients):
                    for la, lb in zip(jax.tree_util.tree_leaves(ca.params),
                                      jax.tree_util.tree_leaves(cb.params)):
                        leaf_err = max(leaf_err,
                                       float(jnp.max(jnp.abs(la - lb))))
                if _ledger_events(a.ledger) != _ledger_events(b.ledger):
                    problems.append(f"sharded/{label}/{method}: ledger "
                                    f"differs from the unsharded fold")
                if a.diagnostics.get("device_fold") != devices:
                    problems.append(
                        f"sharded/{label}/{method}: device_fold="
                        f"{a.diagnostics.get('device_fold')}")
            log(f"[sharded] {label}/{method}: metric max |diff| "
                f"{metric_err:.3e}, param leaf max |diff| {leaf_err:.3e}")
            if not (metric_err <= PARITY and leaf_err <= PARITY):
                problems.append(f"sharded/{label}/{method}: parity "
                                f"{metric_err:.3e} / {leaf_err:.3e} > "
                                f"{PARITY}")


# ---------------------------------------------------------------- main --
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-fold check on four chips")
    args = ap.parse_args(argv)

    info = device_info()
    if info["platform"] != "tpu" or interpret_mode():
        print(f"chip_smoke: needs a TPU with compiled Pallas kernels; found "
              f"{info['platform']} ({info['kind']}), interpret="
              f"{interpret_mode()}", file=sys.stderr)
        return 2
    if info["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {info['count']} "
              f"device(s) visible", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    log(f"[device] {info['kind']} x{info['count']} ({info['platform']}); "
        f"compile cache {cache}")
    os.makedirs(OUT_DIR, exist_ok=True)

    problems: list = []
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(problems, devices=4)
    else:
        kernel_phase(problems)
        trained = train_phase(problems)
        grid, cfg = trained[("one_shot", "kernel")]
        bundle = scenarios.build(scenarios.get(GROUP[0]), seed=SEEDS[0])
        serve_phase(problems, grid[0][0], bundle.spec, cfg, bundle.split)
    log(f"[time] total {time.perf_counter() - t0:.3f} s (one smoke run)")

    if problems:
        for p in problems:
            print(f"chip_smoke FAILED: {p}", file=sys.stderr)
        return 1
    info = device_info()
    if args.chips == 4:
        info["count"] = 4
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
