"""The control of each cell comes out as not correct: the plain reference
computed at the next precision below the configuration's (three bfloat16
passes for float32 at ``highest``), put in the program's place, fails
one of the cell's limits.  On the chip this was run at each cell's own
size on three seeds (PERF.md); here at a size a test run holds."""
from __future__ import annotations

import copy

from harness import serve_loop, solve_loop
from harness.cells import load_cell


def test_epsilon_control_fails_its_limits():
    cell = copy.deepcopy(load_cell("epsilon-dagd"))
    # the configuration's recipe at 8,192 x 1,024; a smaller lam keeps the
    # fixed point as sensitive to rounding as at the full size
    cell.config["instance_params"].update(n=8192, d=1024, lam=1e-6)
    got = solve_loop.control_readings(cell, 2147483653)
    assert got["ledger_mismatch"] == 0
    assert got["w_rel"] > solve_loop.LIMITS["w_rel"], got


def test_thm2_control_fails_its_limits():
    cell = load_cell("thm2-serve")          # the cell's own spec sizes
    got = serve_loop.control_readings(cell, 2147483653, seconds=6.0)
    failed = [k for k, v in got.items() if v > serve_loop.LIMITS[k]]
    assert failed, got
