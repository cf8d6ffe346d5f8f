"""The serving benchmark: latency/throughput + parity gates on a deployed
artifact.

Loads (or trains and exports) a ``TrainedVFLModel`` and drives it through
``repro.launch.vfl_serve`` at batch 1 / 64 / 1024, reporting per-batch-size
p50/p99 latency and throughput as typed serving rows (``repro.core.rows``
— the SAME row schema the frontier gate consumes). Three contracts are
machine-checked against ``serving_baseline.json``:

* PARITY — batched fused predictions match the artifact's unbatched
  reference forward (``TrainedVFLModel.predict_logits``) at 1e-5 per
  batch size;
* RECOMPILE — the fused forward adds ZERO fresh ``"serving"``-domain
  session-cache misses after the first batch shape (capacities change,
  the cached program does not: its key carries no batch width);
* LATENCY — p50 must stay under the baseline's per-batch-size ceiling
  and throughput above its floor, where the baseline pins one (ceilings
  are optional — ``null`` skips, for CI hosts with noisy clocks).

CI wiring (.github/workflows/ci.yml, job ``bench-smoke``)::

    python -m benchmarks.serving --train --smoke --check-gate \
        --save-artifact artifact-smoke --out BENCH_serving.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro import scenarios
from repro.core import ProtocolConfig
from repro.core import rows as result_rows
from repro.core.protocol import run_one_shot
from repro.checkpoint import load_artifact, save_artifact
from repro.engine import session_cache_stats
from repro.launch import vfl_serve
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.vfl_serve import ServingEngine

BASELINE_PATH = os.path.join(os.path.dirname(__file__),
                             "serving_baseline.json")

BATCH_SIZES = (1, 64, 1024)
PARITY_ATOL = 1e-5
TRAIN_SCENARIO = "hard/overlap-32"


def train_artifact(scenario: str = TRAIN_SCENARIO, seed: int = 0,
                   smoke: bool = True):
    """One-shot-train one scenario seed and export it as the deployment
    artifact the bench serves (what ``--train`` runs)."""
    spec = scenarios.get(scenario)
    bundle = scenarios.build(spec, seed=seed, smoke=smoke)
    cfg = ProtocolConfig(
        client_epochs=spec.budget("client_epochs", 8),
        server_epochs=spec.budget("server_epochs", 30),
    )
    res = run_one_shot(jax.random.PRNGKey(seed), bundle.split,
                       bundle.extractors, bundle.ssl_cfgs, cfg)
    return res.to_artifact(spec, cfg=cfg, split=bundle.split)


def _max_abs_diff(a: jnp.ndarray, b: jnp.ndarray) -> float:
    return float(jnp.max(jnp.abs(a - b)))


def bench_artifact(art, batch_sizes=BATCH_SIZES, requests: int = 8,
                   seed: int = 0) -> list:
    """Serve ``requests`` synthetic batches at every batch size; one typed
    serving row per size carrying the latency summary, the parity error
    against the unbatched reference, and the fresh serving-domain session
    builds the size triggered (0 for every size after the first)."""
    rows = []
    for i, bs in enumerate(batch_sizes):
        engine = ServingEngine(art, capacity=bs)
        reqs = vfl_serve.synthetic_requests(art, requests, bs,
                                            seed=seed + i)
        misses0 = session_cache_stats("serving")["misses"]
        outs, rec = vfl_serve.serve_traffic(engine, reqs)
        fresh = session_cache_stats("serving")["misses"] - misses0
        # parity: the fused masked-batched forward vs the per-request
        # unbatched reference oracle, on the first request
        ref = art.predict_logits(list(reqs[0]))
        parity = _max_abs_diff(outs[0], ref)
        s = rec.summary()
        row = result_rows.serving_row(
            "p50_ms", s["p50_ms"],
            scenario=art.scenario,
            batch=bs,
            capacity=engine.capacity,
            requests=len(reqs),
            p99_ms=s["p99_ms"],
            mean_ms=s["mean_ms"],
            rows_per_s=s["rows_per_s"],
            parity_max_abs=parity,
            cache_misses=fresh,
            first_shape=(i == 0),
            homogeneous=art.parties_are_homogeneous,
            num_parties=art.num_parties,
        )
        rows.append(row)
        print(f"{art.scenario:>18s} serve b={bs:<5d} "
              f"p50={s['p50_ms']:8.2f}ms p99={s['p99_ms']:8.2f}ms "
              f"{s['rows_per_s']:10.0f} rows/s "
              f"parity={parity:.2e} fresh_builds={fresh}", flush=True)
    return rows


def check_serving_gate(rows, baseline_path: str = BASELINE_PATH) -> list:
    """The serving regression gate; returns violation strings. Consumes
    the same typed row shape as the frontier's ``check_gate``."""
    problems = []
    with open(baseline_path) as fh:
        baseline = json.load(fh)
    atol = baseline.get("parity_atol", PARITY_ATOL)
    ceilings = baseline.get("max_p50_ms", {})
    floors = baseline.get("min_rows_per_s", {})
    serving = [r for r in rows if r.get("kind") == "serving"]
    if not serving:
        return ["no serving rows to gate"]
    for r in serving:
        bs = str(r["batch"])
        if r["parity_max_abs"] > atol:
            problems.append(
                f"batch {bs}: batched-vs-unbatched parity "
                f"{r['parity_max_abs']:.2e} > {atol:.0e}")
        if not r.get("first_shape") and r["cache_misses"] != 0:
            problems.append(
                f"batch {bs}: {r['cache_misses']} fresh serving-session "
                f"builds after the first batch shape — the fused forward "
                f"must re-serve ONE cached program at every capacity")
        ceiling = ceilings.get(bs)
        if ceiling is not None and r["metric"] > ceiling:
            problems.append(
                f"batch {bs}: p50 {r['metric']:.2f}ms > baseline ceiling "
                f"{ceiling:.2f}ms")
        floor = floors.get(bs)
        if floor is not None and r["rows_per_s"] < floor:
            problems.append(
                f"batch {bs}: throughput {r['rows_per_s']:.0f} rows/s < "
                f"baseline floor {floor:.0f}")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--artifact", help="serve an existing artifact dir")
    src.add_argument("--train", action="store_true",
                     help=f"train {TRAIN_SCENARIO} (one seed) and serve it")
    ap.add_argument("--smoke", action="store_true",
                    help="train at smoke sizes (CI tier)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch-sizes", type=int, nargs="+",
                    default=list(BATCH_SIZES))
    ap.add_argument("--requests", type=int, default=8,
                    help="timed requests per batch size")
    ap.add_argument("--save-artifact", default=None,
                    help="export the trained artifact here (with --train)")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--check-gate", action="store_true")
    ap.add_argument("--baseline", default=BASELINE_PATH)
    args = ap.parse_args(argv)
    enable_compile_cache()

    t0 = time.time()
    if args.train:
        art = train_artifact(seed=args.seed, smoke=args.smoke)
        print(f"trained {art.scenario}: {art.metric_name}={art.metric:.4f} "
              f"({time.time() - t0:.0f}s)", flush=True)
        if args.save_artifact:
            path = save_artifact(args.save_artifact, art)
            print(f"saved artifact -> {path}")
            # serve what a deployment would: the RELOADED artifact
            art = load_artifact(args.save_artifact)
    else:
        art = load_artifact(args.artifact)

    rows = bench_artifact(art, batch_sizes=tuple(args.batch_sizes),
                          requests=args.requests, seed=args.seed)
    blob = {
        "scenario": art.scenario,
        "seed": args.seed,
        "batch_sizes": list(args.batch_sizes),
        "wall_s": round(time.time() - t0, 2),
        "rows": rows,
    }
    with open(args.out, "w") as fh:
        json.dump(blob, fh, indent=2)
    print(f"wrote {args.out}: {len(rows)} rows in {blob['wall_s']:.0f}s")

    if args.check_gate:
        problems = check_serving_gate(rows, args.baseline)
        if problems:
            for p in problems:
                print(f"SERVING GATE VIOLATION: {p}", file=sys.stderr)
            return 1
        print("serving gate: parity at 1e-5, one cached fused program "
              "across batch shapes, latency within baseline")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
