"""Readings that the limits of ``correct`` are set from, on the chip at a
cell's own size: for each seed, the program's compared numbers (the lower
reading), the control's (the reference one precision below the
configuration's) and those of faults planted in the program's output or
in a reference session put in its place (``fault_numbers``), each judged by
the cell's limits.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 ... [--seconds 3]

One process reads every seed, so the compiled programs load once. Each seed
prints one JSON line; the benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def fault_numbers(driver, state) -> dict:
    """Faults planted in what the timed path produced, or a reference
    session standing in for the program's: an answer altered, half of the
    answers zeroed, step-3 labels altered, half of each SSL batch left out
    (the mean taken over the rest)."""
    import jax.numpy as jnp

    def alter(fn):
        recs = []
        for rec in state.calls:
            rec = dict(rec)
            fn(rec)
            recs.append(rec)
        return driver.numbers(state, recs)

    return {
        "answer_altered": alter(lambda r: r.update(eval=[
            (reps, lg.at[0].add(10.0)) for reps, lg in r["eval"]])),
        "half_answers_zeroed": alter(lambda r: r.update(eval=[
            (reps, lg.at[lg.shape[0] // 2:].set(0.0))
            for reps, lg in r["eval"]])),
        "labels_altered": alter(lambda r: r.update(kmeans=[
            (g, [jnp.roll(lab, 7) for lab in labs])
            for g, labs in r["kmeans"]])),
        "half_batch": driver.numbers(state, stand_in={"half": True}),
    }


def session_readings(state) -> list:
    """Raw losses of every session compared: per party, the program's
    last-step metrics, and for each reference session kept (the reference,
    the control, the half-batch fault) its first, last and median loss and
    the mean of its last 16 steps."""
    import numpy as np

    out = []
    for (ci, s, p, dtype, half), sess in sorted(state.sessions.items()):
        losses = np.asarray(sess.losses, np.float64)
        out.append({"call": int(ci), "entry": s, "party": p, "dtype": dtype,
                    "half": half, "first": float(losses[0]),
                    "last": float(losses[-1]),
                    "median": float(np.median(np.abs(losses))),
                    "last16": float(np.mean(losses[-16:])),
                    "program": state.calls[ci]["ssl"][0]["metrics"][s][p]})
    return out


def leaf_readings(driver, state) -> list:
    """Per party of each checked call: every leaf's change norm in the
    program's session and in each reference session kept (by dtype and
    ``half``), and the norm of the reference's first gradient."""
    import jax

    leaves = jax.tree_util.tree_leaves
    out = []
    for ci in driver.checked_calls(state):
        ssl = state.calls[ci]["ssl"][0]
        for s, tasks in enumerate(ssl["tasks"]):
            for p, task in enumerate(tasks):
                before = {"extractor": task.params.extractor,
                          "head": task.params.head}
                got = ssl["params"][s][p]
                got = {"extractor": got.extractor, "head": got.head}
                change = lambda after: [driver._norm(a - b) for b, a in zip(
                    leaves(before), leaves(after))]
                row = {"call": int(ci), "entry": s, "party": p,
                       "program": change(got)}
                for (c2, s2, p2, dtype, half), sess in state.sessions.items():
                    if (c2, s2, p2) == (ci, s, p):
                        row[f"{dtype}{'_half' if half else ''}"] = change(
                            sess.params)
                        if dtype == "float32" and not half:
                            row["first_grad"] = [driver._norm(g) for g in
                                                 leaves(sess.first_grad)]
                out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    config = harness.load_config(cell["config"])
    try:
        harness.require_chips(int(cell["chips"]))
    except harness.NoChip as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    driver = harness.load_driver(cell["driver"])
    for seed in args.seeds:
        t0 = time.perf_counter()
        state = driver.setup(cell, config, seed)
        win = driver.window(state, args.seconds, harness.Tracer(False))
        limits = cell["limits"]
        judged = lambda numbers: {
            "correct": harness.judge(
                numbers, {k: v for k, v in limits.items() if k in numbers})[0],
            "numbers": numbers}
        line = {"seed": seed, "attempted": win.attempted,
                "end_to_end": win.end_to_end,
                "program": judged(driver.check(state, win)),
                "program_detail": driver.numbers(state, detail=True),
                "control": judged(driver.control_numbers(state, detail=True)),
                "faults": {name: judged(numbers) for name, numbers in
                           fault_numbers(driver, state).items()},
                "sessions": session_readings(state),
                "leaves": leaf_readings(driver, state),
                "seconds": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
        del state
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
