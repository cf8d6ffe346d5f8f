"""Communication accounting at the PAPER's exact scale (Tab. 1 columns).

Pure ledger arithmetic — no training — cross-checking the implementation's
accounting against the paper's reported numbers: one-shot = 3 comm times and
0.79–6.3 MB; vanilla = 2 comm times/iter and 262–2094 MB; ratio ≥ 330×.
"""
from __future__ import annotations

from repro.core.comm import CommLedger

REP_DIM = 128      # WideResNet20 feature dim at the paper's setting
BATCH = 32
CLASSES = 10


def vanilla_ledger(iterations: int) -> CommLedger:
    led = CommLedger()
    for _ in range(iterations):
        r1, r2 = led.next_round(), led.next_round()
        for c in range(2):
            led.log_bytes(c, "up", "reps", BATCH * REP_DIM * 4, round=r1)
            led.log_bytes(c, "down", "grads", BATCH * REP_DIM * 4, round=r2)
    return led


def one_shot_ledger(n_o: int) -> CommLedger:
    led = CommLedger()
    r1, r2, r3 = led.next_round(), led.next_round(), led.next_round()
    for c in range(2):
        led.log_bytes(c, "up", "reps", n_o * REP_DIM * 4, round=r1)
        led.log_bytes(c, "down", "grads", n_o * REP_DIM * 4 + 4, round=r2)
        led.log_bytes(c, "up", "reps2", n_o * REP_DIM * 4, round=r3)
    return led


def few_shot_ledger(n_o: int, n_u: int) -> CommLedger:
    led = one_shot_ledger(n_o)
    r3 = max(e.round for e in led.events)
    r4, r5 = led.next_round(), led.next_round()
    for c in range(2):
        led.log_bytes(c, "up", "reps_unaligned", n_u * REP_DIM * 4, round=r3)
        led.log_bytes(c, "down", "probs", n_u * 4, round=r4)
        led.log_bytes(c, "up", "reps_final", n_o * REP_DIM * 4, round=r5)
    return led


def main() -> None:
    # the paper's Tab. 1 iteration counts per overlap size
    paper_iters = {256: 4000, 512: 8000, 1024: 16000, 2048: 32000}
    total_cifar = 50000
    print("name,us_per_call,derived")
    for n_o, iters in paper_iters.items():
        van = vanilla_ledger(iters)
        one = one_shot_ledger(n_o)
        n_u = (total_cifar - n_o) // 2
        few = few_shot_ledger(n_o, n_u)
        ratio = van.total_bytes() / one.total_bytes()
        print(f"comm/vanilla/overlap{n_o},0,"
              f"mb={van.total_megabytes():.1f};times={van.comm_times()}")
        print(f"comm/one_shot/overlap{n_o},0,"
              f"mb={one.total_megabytes():.2f};times={one.comm_times()}")
        print(f"comm/few_shot/overlap{n_o},0,"
              f"mb={few.total_megabytes():.2f};times={few.comm_times()}")
        print(f"comm/reduction/overlap{n_o},0,ratio={ratio:.0f}x")
        assert one.comm_times() == 3 and few.comm_times() == 5
        assert ratio > 300, ratio


if __name__ == "__main__":
    main()
