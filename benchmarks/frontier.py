"""The comm-accuracy frontier: every method x every scenario, one artifact.

Reproduces the paper's comparative claims (Tab. 1-4 ordering: one-shot /
few-shot VFL vs iterative VFL under limited overlap) as a machine-checkable
benchmark. For each scenario in the registry selection it runs

    one_shot   -- Alg. 1 (3 comm times)
    few_shot   -- Alg. 2 (5 comm times)
    iterative  -- SplitNN-style vanilla VFL (2 comm times / iteration)
    fedcvt     -- FedCVT-style semi-supervised cross-view baseline

over ``--seeds N`` seeds (default 1). The paper's headline claims are
*statistical* — orderings that hold across runs, not at one seed — so the
sweep emits one row per (scenario, method, seed) plus, for N > 1, one
AGGREGATE row per (scenario, method) carrying metric mean/std/min/max.

Execution is GROUPED (DESIGN.md §12): the scenario selection is first
partitioned by ``scenarios.group_scenarios`` into stackable buckets —
entries whose party semantics (the engine's ``parties_are_homogeneous``
predicate, party position by party position), split shapes, and training
budgets all match — and each group's C scenarios × S seeds go through
``repro.core.protocol.run_scenarios_seeds`` as ONE folded sweep per
method: the protocol methods on the vmapped S·C·K client axis (DESIGN.md
§10), the iterative baselines as one ``vmap``-of-scan over S·C stacked
whole-session carries (DESIGN.md §11) — with zero fresh compiled-session
builds beyond each group's first member, so catalog coverage grows while
wall-clock grows far sublinearly.

Each row records metric (AUC or accuracy), ledger bytes, comm times,
wall-clock (per-seed rows: the method's whole-GROUP sweep wall amortized
over its C×S entries), ``group_size`` + ``scenario_fold`` + ``seed_fold``
(the partitioner's ground truth vs the fold the runner actually
executed), and ``cache_misses`` — fresh compiled-session builds the
method's whole group sweep triggered (the engine-wide session-cache
counters of DESIGN.md §9; ``jax.jit`` may still re-specialize a cached
session per input shape, so this counts trace-level program builds, not
individual XLA compilations). The blob-level ``session_cache`` field
carries the per-domain hit/miss totals and ``groups`` the partition.

CI wiring (.github/workflows/ci.yml, job ``bench-frontier`` — one of five
parallel bench legs; ``bench-kernels`` / ``bench-sharded`` /
``bench-faults`` re-run this module on focused ``--scenarios`` slices
with ``--use-kernels`` / ``--devices 2`` / the fault/* family, and
``bench-serving`` runs ``benchmarks.serving``)::

    python -m benchmarks.frontier \
        --smoke --seeds 2 --check-gate

``--smoke`` runs the FULL registry catalog at CI-tractable smoke sizes
(grouped execution is what makes that affordable); the scheduled nightly
tier (ci.yml job ``bench-frontier-nightly``) runs the frontier-tagged set
at paper sizes with ``--seeds 4``. ``--check-gate`` then enforces the
paper's headline ordering on the fresh results, per baseline-listed
scenario with overlap<=64 (dominance claims are pinned per scenario in
``frontier_baseline.json``; unlisted scenarios get only the invariance
and fold-discipline checks):

* bytes: one-shot must move >= 100x fewer bytes than iterative (bytes are
  shape-functions — seed-invariant, asserted by run_seeds);
* MEAN margin: mean over seeds of (one-shot metric - iterative metric)
  must clear the scenario's ``min_mean_margin`` floor from
  ``benchmarks/frontier_baseline.json`` (default: > 0);
* WORST seed: no single seed's margin may fall below ``min_worst_margin``
  (default: >= 0 — one-shot never loses a seed);
* FEW-SHOT margins, same two statistics against the
  ``fewshot_min_mean_margin`` / ``fewshot_min_worst_margin`` floors —
  few-shot is the framework's accuracy ceiling, so its comparative claim
  is gated alongside one-shot's;
* one-shot's ledger bytes must not regress above the recorded baseline.

It also requires, on every row, ``seed_fold`` to cover the sweep's seed
count and ``scenario_fold`` to equal the row's recorded ``group_size``
(the seed and scenario folds of DESIGN.md §10-12 must not silently
degrade to per-seed or per-scenario loops).

``--devices N`` (DESIGN.md §14) shards every folded sweep's stacked
S·C·K axis over an N-device launch mesh (forcing N host devices first on
CPU-only machines); rows record ``device_fold`` and the blob the mesh,
and ``--check-gate`` then also requires every folded row to have actually
sharded (``device_fold == N``).

Fault-injected scenarios (DESIGN.md §16) sweep like any others — the
catalog's fault/* members attach a ``FaultSpec`` and the group runner
forwards the C×S fault grid to ``run_scenarios_seeds`` — and the gate
adds the graceful-degradation floors of :func:`_check_fault_rows`: a
gated FULL sweep must contain fault rows at all, dropout rows must lose
exactly one party (with ledger-visible retry cost on the iterative
methods), and each faulted scenario's one-shot mean may trail its
fault-free twin by at most ``max_oneshot_drop`` (``fault_families`` in
``frontier_baseline.json``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax

from repro import engine, scenarios
from repro.core import IterativeConfig, ProtocolConfig
from repro.core import rows as result_rows
from repro.core import runners as runner_registry
from repro.core.protocol import run_scenarios_seeds
from repro.engine import session_cache_stats, session_cache_stats_by_domain
from repro.launch.compile_cache import enable_compile_cache

BASELINE_PATH = os.path.join(os.path.dirname(__file__), "frontier_baseline.json")

METHODS = ("one_shot", "few_shot", "iterative", "fedcvt")


def _aggregate_row(seed_rows) -> dict:
    """One (scenario, method) summary row over the per-seed rows: the mean
    metric doubles as ``metric`` so every consumer of the per-seed schema
    can read aggregate rows too."""
    metrics = [r["metric"] for r in seed_rows]
    mean = sum(metrics) / len(metrics)
    var = sum((m - mean) ** 2 for m in metrics) / len(metrics)
    row = dict(seed_rows[0])
    row.update(
        seed="aggregate",
        aggregate=True,
        num_seeds=len(seed_rows),
        metric=mean,
        metric_mean=mean,
        metric_std=var ** 0.5,
        metric_min=min(metrics),
        metric_max=max(metrics),
    )
    return row


def runner_cfgs(spec, methods=METHODS, devices=None,
                 use_kernels: bool = False) -> dict:
    """Resolve every method through THE runner registry
    (``repro.core.runners``): the entry supplies the runner callable, its
    ``kind`` picks the config family the scenario budgets parameterize.
    ``devices`` threads the launch mesh (DESIGN.md §14) into both config
    families so every folded sweep shards its stacked S·C·K axis;
    ``use_kernels`` flips the protocol methods onto the Pallas kernel
    route (batched grids over the same stacked axis, DESIGN.md §15 — the
    iterative baselines have no kernel-served hot-spot, so their config is
    untouched)."""
    pcfg = ProtocolConfig(
        client_epochs=spec.budget("client_epochs", 8),
        server_epochs=spec.budget("server_epochs", 30),
        mesh=devices,
        use_kernels=use_kernels,
    )
    if spec.fewshot_threshold is not None:
        pcfg = dataclasses.replace(pcfg,
                                   fewshot_threshold=spec.fewshot_threshold)
    icfg = IterativeConfig(iterations=spec.budget("iterations", 300),
                           mesh=devices)
    cfg_by_kind = {"protocol": pcfg, "iterative": icfg}
    return {m: (runner_registry.get(m).runner,
                cfg_by_kind[runner_registry.get(m).kind])
            for m in methods}


def build_bundles(spec, seeds, smoke: bool):
    """One built bundle per seed of one scenario."""
    return [scenarios.build(spec, seed=s, smoke=smoke) for s in seeds]


def run_scenario_group(bundles_per_scenario, seeds, methods=METHODS,
                       devices=None, use_kernels: bool = False):
    """Run every method on one partitioner GROUP of scenarios over all
    ``seeds``: each method's whole group — C scenarios × S seeds — goes
    through ``run_scenarios_seeds`` as ONE folded sweep (DESIGN.md §12;
    a single scenario is simply the C = 1 width). ``bundles_per_scenario``
    is the C×S grid of built bundles (``[c][s]``). ``devices`` shards each
    folded sweep's stacked axis over that many devices (DESIGN.md §14) —
    every row's ``device_fold`` diagnostic records whether it did. Returns
    result rows.
    """
    specs = [bs[0].spec for bs in bundles_per_scenario]
    group_size = len(specs)
    cfgs = runner_cfgs(specs[0], methods, devices=devices,
                       use_kernels=use_kernels)
    # the engine's own homogeneity predicate: apply-fn identity + equal
    # SSL configs + equal per-party feature shapes. Heterogeneous feature
    # blocks (e.g. credit/feature-skew) — or equal-dim parties with
    # *different* architectures — train one session per party signature
    # and may hand k-means ragged gradients, so the kernel-fold gate skips
    # those rows. ONE decision per group: the partitioner's signature
    # makes party semantics uniform across members, so scenario 0 speaks
    # for all of them
    b0 = bundles_per_scenario[0][0]
    vmap_eligible = engine.parties_are_homogeneous(
        b0.extractors, b0.ssl_cfgs, [x.shape for x in b0.split.aligned])
    # a group carrying any FaultSpec threads the C×S fault grid through the
    # SAME folded sweep (DESIGN.md §16): faults are per-entry data, excluded
    # from the fold signature, so fault/* members and their fault-free twin
    # stack into one program
    fault_kw = {}
    if any(spec.fault is not None for spec in specs):
        fault_kw["faults"] = [[spec.fault for _ in seeds] for spec in specs]
    rows = []
    for method in methods:
        runner, cfg = cfgs[method]
        misses0 = session_cache_stats()["misses"]
        results = run_scenarios_seeds(
            runner,
            [[jax.random.PRNGKey(s) for s in seeds] for _ in specs],
            [[b.split for b in bs] for bs in bundles_per_scenario],
            [[b.extractors for b in bs] for bs in bundles_per_scenario],
            [[b.ssl_cfgs for b in bs] for bs in bundles_per_scenario],
            cfg, **fault_kw)
        misses = session_cache_stats()["misses"] - misses0
        for spec, scen_results in zip(specs, results):
            seed_rows = []
            for seed, res in zip(seeds, scen_results):
                # the one typed row builder every gate consumes
                # (repro.core.rows): summary_row() context rides along here
                row = result_rows.training_row(
                    res,
                    scenario=spec.name,
                    seed=seed,
                    method=method,
                    cache_misses=misses,          # whole-group fresh builds
                    group_size=group_size,        # partitioner ground truth
                    vmap_eligible=vmap_eligible,
                    use_kernels=use_kernels,
                    overlap=spec.overlap,
                    num_parties=spec.num_parties,
                    modality=spec.modality,
                )
                seed_rows.append(row)
                print(
                    "{scenario:>18s} {method:>9s} s{seed:<2d} "
                    "{metric_name}={metric:.4f} bytes={comm_bytes:>10d} "
                    "times={comm_times:>6d}".format(**row),
                    flush=True,
                )
            rows.extend(seed_rows)
            if len(seed_rows) > 1:
                agg = _aggregate_row(seed_rows)
                rows.append(agg)
                print(
                    "{scenario:>18s} {method:>9s} agg "
                    "{metric_name}={metric_mean:.4f}±{metric_std:.4f} "
                    "[{metric_min:.4f}, {metric_max:.4f}]".format(**agg),
                    flush=True,
                )
    return rows


def run_scenario(spec, seeds, smoke: bool, methods=METHODS, devices=None,
                 use_kernels: bool = False):
    """Run every method on ONE scenario over all ``seeds`` — the width-1
    group case of :func:`run_scenario_group`."""
    return run_scenario_group([build_bundles(spec, seeds, smoke)], seeds,
                              methods=methods, devices=devices,
                              use_kernels=use_kernels)


def _check_margins(name: str, method_rows: dict, its: dict, label: str,
                   min_mean: float, min_worst: float, problems: list) -> None:
    """Mean-margin + worst-seed dominance of one method over iterative."""
    shared_seeds = sorted(set(method_rows) & set(its))
    if not shared_seeds:
        return
    margins = {s: method_rows[s]["metric"] - its[s]["metric"]
               for s in shared_seeds}
    mean_margin = sum(margins.values()) / len(margins)
    if mean_margin <= min_mean:
        problems.append(
            f"{name}: {label} mean margin over iterative "
            f"{mean_margin:+.4f} <= floor {min_mean:+.4f} "
            f"(seeds {shared_seeds})"
        )
    worst_seed = min(margins, key=margins.get)
    if margins[worst_seed] < min_worst:
        problems.append(
            f"{name}: {label} worst-seed margin {margins[worst_seed]:+.4f} "
            f"(seed {worst_seed}) < floor {min_worst:+.4f}"
        )


def _check_fault_rows(per_seed, baseline, expect_faults: bool,
                      problems: list) -> None:
    """Graceful-degradation gate over the fault/* rows (DESIGN.md §16).

    Per ``fault_families`` entry in the baseline file: the whole family
    must be present (``required``); dropout rows must record one party
    lost (``parties_survived == K-1``) and — on the iterative methods —
    ledger-visible retry/timeout cost; every protocol fault row must carry
    ``degraded_metric``; and the one-shot MEAN metric of each faulted
    scenario may fall at most ``max_oneshot_drop`` below its fault-free
    twin's (``baseline_scenario``). A gated full sweep with ZERO fault
    rows is itself a violation (``expect_faults``) — degradation coverage
    must not silently vanish from CI, mirroring the missing-few-shot rule.
    """
    fams = baseline.get("fault_families", {})
    fault_rows = [r for r in per_seed if "fault_kind" in r]
    if not fault_rows:
        if expect_faults:
            problems.append(
                "no fault-injected rows in a gated sweep — the "
                "graceful-degradation gate cannot be evaluated (sweep the "
                "full catalog, or pass --scenarios explicitly for partial "
                "sweeps)"
            )
        return
    for fam, fspec in fams.items():
        rows_f = [r for r in fault_rows
                  if r["scenario"].startswith(fam + "/")]
        if not rows_f:
            continue
        present = {r["scenario"] for r in rows_f}
        missing = sorted(set(fspec.get("required", ())) - present)
        if missing:
            problems.append(
                f"fault family {fam!r}: scenarios {missing} missing from "
                f"the sweep — the degradation claim needs the whole family"
            )
        for r in rows_f:
            num_parties = r.get("num_parties")
            survived = r.get("parties_survived")
            if r.get("fault_kind") == "dropout":
                if survived != num_parties - 1:
                    problems.append(
                        f"{r['scenario']} seed {r['seed']}: {r['method']} "
                        f"dropout row records parties_survived={survived} "
                        f"(expected {num_parties - 1} of {num_parties})"
                    )
                if r["method"] in ("iterative", "fedcvt") \
                        and (r.get("fault_retry_rounds", 0) < 1
                             or r.get("fault_retry_bytes", 0) < 1):
                    problems.append(
                        f"{r['scenario']} seed {r['seed']}: {r['method']} "
                        f"dropout row shows no retry/timeout cost in the "
                        f"ledger (fault_retry_rounds="
                        f"{r.get('fault_retry_rounds')}, fault_retry_bytes="
                        f"{r.get('fault_retry_bytes')})"
                    )
            elif survived != num_parties:
                problems.append(
                    f"{r['scenario']} seed {r['seed']}: {r['method']} "
                    f"{r.get('fault_kind')} row records "
                    f"parties_survived={survived} (expected {num_parties})"
                )
            if r["method"] in ("one_shot", "few_shot") \
                    and r.get("degraded_metric") is None:
                problems.append(
                    f"{r['scenario']} seed {r['seed']}: {r['method']} fault "
                    f"row carries no degraded_metric"
                )
        base_name = fspec.get("baseline_scenario")
        max_drop = fspec.get("max_oneshot_drop")
        if base_name is None or max_drop is None:
            continue
        base_ones = [r["metric"] for r in per_seed
                     if r["scenario"] == base_name
                     and r["method"] == "one_shot"]
        if not base_ones:
            problems.append(
                f"fault family {fam!r}: fault-free twin {base_name!r} has "
                f"no one_shot rows to measure degradation against"
            )
            continue
        base_mean = sum(base_ones) / len(base_ones)
        for name in sorted(present - {base_name}):
            vals = [r["metric"] for r in fault_rows
                    if r["scenario"] == name and r["method"] == "one_shot"]
            if not vals:
                continue
            mean = sum(vals) / len(vals)
            if mean < base_mean - max_drop:
                problems.append(
                    f"{name}: one-shot degraded mean metric {mean:.4f} "
                    f"fell more than {max_drop:.3f} below the fault-free "
                    f"twin {base_name} ({base_mean:.4f}) — graceful "
                    f"degradation broke"
                )


def check_gate(rows, baseline_path: str = BASELINE_PATH,
               devices=None, use_kernels: bool = False,
               expect_faults: bool = False) -> list:
    """The CI regression gate. Returns a list of violation strings.

    Point estimates upgraded to seed statistics: the one-shot-vs-iterative
    AND few-shot-vs-iterative orderings are enforced on the MEAN margin
    across seeds plus a worst-seed floor, instead of a single seed's
    (possibly lucky) point comparison — few-shot is the framework's
    accuracy ceiling, so its margins are gated alongside one-shot's.

    ``devices`` (a sharded ``--devices N`` sweep) additionally requires
    every per-seed row to record ``device_fold == devices`` — the mesh
    must not be silently dropped.

    ``use_kernels`` (a ``--use-kernels`` sweep) requires the kernel path to
    have kept the fold (DESIGN.md §15): every stackable protocol row must
    record ``kernel_fold == seed_fold · scenario_fold · num_parties`` (the
    step-③ k-means fold over the whole flat S·C·K batch — no per-entry
    fallback) and every few-shot row ``sdpa_fold == seed_fold ·
    scenario_fold`` (③' folded over the stacked seed axis).

    ``expect_faults`` (set by full gated sweeps) additionally runs the
    graceful-degradation gate over the fault/* rows — and treats a sweep
    with ZERO fault rows as a violation (:func:`_check_fault_rows`).
    """
    problems = []
    per_seed = [r for r in rows if not r.get("aggregate")]
    scenario_names = sorted({r["scenario"] for r in per_seed})

    with open(baseline_path) as fh:
        baseline = json.load(fh)

    _check_fault_rows(per_seed, baseline, expect_faults, problems)

    if use_kernels:
        for r in per_seed:
            if r["method"] not in ("one_shot", "few_shot") \
                    or not r.get("vmap_eligible", False):
                continue   # ragged party zoos legitimately fall back
            flat = r.get("seed_fold", 1) * r.get("scenario_fold", 1)
            want_km = flat * r.get("num_parties", 1)
            if r.get("kernel_fold") != want_km:
                problems.append(
                    f"{r['scenario']} seed {r['seed']}: {r['method']} ran "
                    f"kernel_fold={r.get('kernel_fold')} under --use-kernels "
                    f"(expected {want_km} = seed_fold x scenario_fold x "
                    f"num_parties"
                    + (f"; fallback: {r['kernel_fallback']!r}"
                       if r.get("kernel_fallback") else "")
                    + ") — the step-③ k-means dropped the batched "
                    f"kernel grid"
                )
            if r["method"] == "few_shot" and r.get("sdpa_fold") != flat:
                problems.append(
                    f"{r['scenario']} seed {r['seed']}: few_shot ran "
                    f"sdpa_fold={r.get('sdpa_fold')} under --use-kernels "
                    f"(expected {flat}) — ③' degraded to a per-seed loop"
                )

    if devices is not None:
        for r in per_seed:
            if r.get("device_fold") != devices:
                problems.append(
                    f"{r['scenario']} seed {r['seed']}: {r['method']} "
                    f"recorded device_fold={r.get('device_fold')} under "
                    f"--devices {devices} — the stacked axis did not shard "
                    f"over the launch mesh"
                )

    # seed_fold (the width the runner actually folded) must cover every
    # seed of the sweep
    num_sweep_seeds = len({r["seed"] for r in per_seed})
    for r in per_seed:
        fold = r.get("seed_fold")
        if fold is not None and fold != num_sweep_seeds:
            problems.append(
                f"{r['scenario']} seed {r['seed']}: {r['method']} ran "
                f"seed_fold={fold} — the {num_sweep_seeds}-seed sweep "
                f"fell back to the per-seed loop instead of the "
                f"DESIGN.md §10-11 fold"
            )
        # ... and scenario_fold must cover the row's whole partitioner
        # group: group_size is the ground truth the bench recorded, so
        # a mismatch means the grouped sweep silently degraded to the
        # per-scenario loop (e.g. a shape drift broke the stack)
        gsize = r.get("group_size")
        if gsize is not None and r.get("scenario_fold") != gsize:
            problems.append(
                f"{r['scenario']} seed {r['seed']}: {r['method']} ran "
                f"scenario_fold={r.get('scenario_fold')} against a "
                f"size-{gsize} group — the grouped sweep fell back to "
                f"the per-scenario loop instead of the DESIGN.md §12 "
                f"fold"
            )

    for name in scenario_names:
        ones = {r["seed"]: r for r in per_seed
                if r["scenario"] == name and r["method"] == "one_shot"}
        fews = {r["seed"]: r for r in per_seed
                if r["scenario"] == name and r["method"] == "few_shot"}
        its = {r["seed"]: r for r in per_seed
               if r["scenario"] == name and r["method"] == "iterative"}
        if not ones:
            continue
        one0 = next(iter(ones.values()))
        one_bytes = {r["comm_bytes"] for r in ones.values()}
        if len(one_bytes) != 1:
            problems.append(
                f"{name}: one-shot bytes differ across seeds "
                f"{sorted(one_bytes)} — communication must be seed-invariant"
            )
        # dominance claims (bytes ratio + margins + bytes regression) are
        # pinned per scenario in the baseline file: scenarios without an
        # entry — e.g. the full smoke catalog's image/credit rows, whose
        # iteration budgets make no 100x bytes claim — only get the
        # seed-invariance and fold-discipline checks above
        base = baseline.get(name)
        if base is None:
            continue
        if base.get("one_shot_bytes") is not None \
                and one0["comm_bytes"] > base["one_shot_bytes"]:
            problems.append(
                f"{name}: one-shot bytes regressed "
                f"{one0['comm_bytes']} > baseline {base['one_shot_bytes']}"
            )
        if not its or one0["overlap"] > 64:
            continue
        it0 = next(iter(its.values()))
        ratio = it0["comm_bytes"] / max(one0["comm_bytes"], 1)
        if ratio < 100.0:
            problems.append(
                f"{name}: one-shot bytes advantage {ratio:.0f}x < 100x"
            )
        _check_margins(name, ones, its, "one-shot",
                       base.get("min_mean_margin", 0.0),
                       base.get("min_worst_margin", 0.0), problems)
        if not fews:
            # a margin that was never measured must not read as a pass
            problems.append(
                f"{name}: no few_shot rows — the few-shot margin gate "
                f"cannot be evaluated (run all METHODS, or drop --check-gate "
                f"for partial sweeps)"
            )
        _check_margins(name, fews, its, "few-shot",
                       base.get("fewshot_min_mean_margin", 0.0),
                       base.get("fewshot_min_worst_margin", 0.0), problems)
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="the full catalog at CI-tractable smoke sizes "
                    "(grouped execution, DESIGN.md §12)")
    ap.add_argument("--seed", type=int, default=0, help="first seed")
    ap.add_argument(
        "--seeds",
        type=int,
        default=1,
        help="number of seeds per scenario (seed .. seed+N-1), executed "
        "seed-batched through the engine (DESIGN.md §10)",
    )
    ap.add_argument("--out", default="BENCH_frontier.json")
    ap.add_argument(
        "--scenarios",
        nargs="+",
        default=None,
        help="explicit scenario names (default: tag-based selection)",
    )
    ap.add_argument(
        "--check-gate",
        action="store_true",
        help="enforce the mean-margin/worst-seed dominance + "
        "bytes-regression gate",
    )
    ap.add_argument("--baseline", default=BASELINE_PATH)
    ap.add_argument(
        "--use-kernels",
        action="store_true",
        help="route the protocol methods' hot-spots (step-③ k-means, "
        "few-shot ③' SDPA) through the batched Pallas kernel grids "
        "(DESIGN.md §15); --check-gate then also pins the kernel-fold "
        "discipline (kernel_fold/sdpa_fold equal the stacked widths)",
    )
    ap.add_argument(
        "--devices",
        type=int,
        default=None,
        help="shard every folded sweep's stacked S*C*K axis over this many "
        "devices (DESIGN.md §14); on CPU hosts the device pool is forced "
        "via --xla_force_host_platform_device_count before jax initializes",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.devices is not None and args.devices > 1:
        # set XLA_FLAGS BEFORE the first backend touch (any device_count()
        # call initializes it and freezes the visible pool) — harmless on
        # non-CPU platforms, where the flag only affects the host backend
        from repro.launch.mesh import forced_host_devices

        forced_host_devices(args.devices)
        if jax.device_count() < args.devices:
            print(f"--devices {args.devices} requested but only "
                  f"{jax.device_count()} visible (was the jax backend "
                  f"already initialized before --devices took effect?)",
                  file=sys.stderr)
            return 2

    if args.scenarios:
        specs = [scenarios.get(n) for n in args.scenarios]
    elif args.smoke:
        # the FULL catalog at smoke sizes: grouped execution (DESIGN.md
        # §12) is what makes every-scenario coverage affordable per-PR —
        # each stackable family compiles once, not once per scenario
        specs = [scenarios.get(n) for n in scenarios.names()]
    else:
        specs = scenarios.by_tag("frontier")
    seeds = list(range(args.seed, args.seed + args.seeds))

    t0 = time.time()
    bundles = [build_bundles(spec, seeds, smoke=args.smoke) for spec in specs]
    groups = scenarios.group_scenarios(
        [(bs[0].spec, bs[0]) for bs in bundles])
    for g in groups:
        print(f"group[{g.size}]: {', '.join(g.names)}", flush=True)
    rows = []
    for g in groups:
        rows.extend(run_scenario_group([bundles[i] for i in g.indices],
                                       seeds, devices=args.devices,
                                       use_kernels=args.use_kernels))

    mesh = engine.resolve_mesh(args.devices)
    blob = {
        "mode": "smoke" if args.smoke else "full",
        "seed": args.seed,
        "seeds": seeds,
        "devices": args.devices,
        "use_kernels": args.use_kernels,
        "mesh": None if mesh is None else {
            "axis_names": list(mesh.axis_names),
            "shape": list(mesh.devices.shape)},
        "groups": [{"scenarios": g.names, "size": g.size} for g in groups],
        "wall_s": round(time.time() - t0, 2),
        "session_cache": session_cache_stats_by_domain(),
        "rows": rows,
    }
    with open(args.out, "w") as fh:
        json.dump(blob, fh, indent=2)
    print(f"wrote {args.out}: {len(rows)} rows in {blob['wall_s']:.0f}s")

    if args.check_gate:
        # an explicit --scenarios list is a partial sweep by construction;
        # tag/smoke selections must carry the fault family (DESIGN.md §16)
        problems = check_gate(rows, args.baseline, devices=args.devices,
                              use_kernels=args.use_kernels,
                              expect_faults=args.scenarios is None)
        if problems:
            for p in problems:
                print(f"GATE VIOLATION: {p}", file=sys.stderr)
            return 1
        print("gate: one-shot AND few-shot dominate iterative (bytes >=100x, "
              "mean margin + worst seed), engine paths as forced, fault/* "
              "degradation within bounds, and bytes match the recorded "
              "baseline")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
