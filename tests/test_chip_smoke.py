"""Rehearsal of ``chip_smoke.py`` on the CPU.

The script's phase functions run here at the registered scenario sizes with
a cut training budget and the Pallas kernels in interpret mode: this finds
wrong paths, arguments and control flow before a chip run does. ``main()``
itself must refuse this host: no TPU means a non-zero exit and no
``"ok": true`` line.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke
from repro import scenarios
from repro.kernels import interpret_mode

_BUDGETS = {"client_epochs": 2, "server_epochs": 3}


def test_phases_rehearse_on_cpu(tmp_path):
    assert interpret_mode()
    problems = []
    chip_smoke.kernel_phase(problems)
    trained = chip_smoke.train_phase(problems, budgets=_BUDGETS)
    grid, cfg = trained[("one_shot", "kernel")]
    bundle = scenarios.build(chip_smoke.GROUP[0], seed=chip_smoke.SEEDS[0])
    chip_smoke.serve_phase(problems, grid[0][0], bundle.spec, cfg, bundle.split, out_dir=str(tmp_path))
    assert problems == []
    assert (tmp_path / "artifact").is_dir()


def test_main_refuses_a_cpu_device(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok": true' not in captured.out
    assert "needs a TPU" in captured.err
