"""Readings that the limits of a few-shot cell's ``correct`` are set from, on
the chip at the cell's own size: for each seed, the program's compared
numbers, the control's (the reference one precision below the
configuration's) and those of the faults ``bench/control.py`` plants in any
protocol cell, and of the few-shot driver's own (``fault_numbers``: the gate
inverted, the estimates rolled by one row, round 2 dropped from the
ledger), each judged by the cell's limits.

    python3 bench/fewshot_control.py --workload <cell> --seeds 11 12 13 ...

One process reads every seed, so the compiled programs load once. Each seed
prints one JSON line; the benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import control, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    config = harness.load_config(cell["config"])
    try:
        harness.require_chips(int(cell["chips"]))
    except harness.NoChip as e:
        print(f"bench/fewshot_control.py: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    driver = harness.load_driver(cell["driver"])
    limits = cell["limits"]

    def judged(numbers):
        return {"correct": harness.judge(numbers, {
            k: v for k, v in limits.items() if k in numbers})[0],
            "numbers": numbers}

    for seed in args.seeds:
        t0 = time.perf_counter()
        state = driver.setup(cell, config, seed)
        win = driver.window(state, 0.0, harness.Tracer(False))
        faults = {**control.fault_numbers(driver, state),
                  **driver.fault_numbers(state)}
        line = {"seed": seed, "attempted": win.attempted,
                "end_to_end": win.end_to_end,
                "program": judged(driver.check(state, win)),
                "program_detail": driver.numbers(state, detail=True),
                "control": judged(driver.control_numbers(state, detail=True)),
                "faults": {name: judged(numbers)
                           for name, numbers in faults.items()},
                "seconds": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
        del state
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
