"""Run one benchmark cell once on the chips it asks for.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's data and weights from ``--seed``, warms up every shape its
traffic uses (set-up), measures for ``--seconds``, checks what the timed
path produced against ``bench/reference``, and prints one JSON line last on
standard output. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics read from a profiler trace. Without a
TPU, or with fewer chips than the cell asks for, it exits 3 and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def main(argv=None, require_tpu: bool = True, workloads_dir=None,
         configs_dir=None, peak=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload, workloads_dir)
    config = harness.load_config(cell["config"], configs_dir)
    chips = int(cell["chips"])
    if require_tpu:
        try:
            harness.require_chips(chips)
        except harness.NoChip as e:
            print(f"bench/run.py: {e}", file=sys.stderr)
            return 3
    harness.enable_compile_cache()
    driver = harness.load_driver(cell["driver"])

    state = driver.setup(cell, config, args.seed)
    setup_s = time.perf_counter() - T_START
    tracer = harness.Tracer(bool(args.trace))
    win = driver.window(state, args.seconds, tracer)
    tracer.stop()
    device = harness.device_info(chips)
    device["memory_peak_bytes"] = harness.memory_peak_bytes(chips)

    breakdown = None
    if args.trace:
        from bench import flops, trace_reduce

        tr = trace_reduce.load(harness.TRACE_DIR)
        devices = tr.devices[:chips]
        start, end = tr.window()
        busy = [trace_reduce.busy_ns(tr.ops[d], start, end) * 1e-9
                for d in devices]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = (end - start) * 1e-9
        ctx = {"trace": tr, "devices": devices, "chips": chips,
               "counters": win.counters, "config": config, "cell": cell,
               "peak": peak or flops.peaks(device["kind"])}
        metrics = harness.per_layer(ctx)
        breakdown = trace_reduce.breakdown(tr, devices)
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for name, (value, unit) in win.end_to_end.items():
            metrics[name] = {"value": value, "unit": unit}

    numbers = driver.check(state, win)
    correct, checks = harness.judge(numbers, cell["limits"])
    readings = {k: v for k, v in numbers.items() if k not in checks}
    for name, value in readings.items():
        print(f"reading {name}: {value!r} (not compared)", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(harness.result_line(correct, win, metrics, device, checks,
                              breakdown, readings))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
