"""The control: the reference in the program's place, its matrix products in
float8, must fail the cell's comparison.  Here at a size a test run holds (the
tiny cell's limits); the readings at each cell's own size come from
``calibrate.py`` on the chip."""

import harness
from tiny import tiny_cell


def test_float8_reference_is_not_correct():
    import jax
    cell = tiny_cell()
    prog = harness.Program(cell, jax.devices()[:1])
    seed = 2**31 + 99
    ref = harness.reference_readings(prog, seed)
    low = harness.reference_readings(prog, seed, low=True)
    got = harness.compare(low, ref)
    over = [k for k in ("loss_gap", "grad_gap", "change_gap") if got[k] > cell.limits[k]]
    assert over, got
    # and the float32 reference against itself reads nothing
    same = harness.compare(harness.reference_readings(prog, seed), ref)
    assert same["grad_gap"] == 0.0 and same["loss_gap"] == 0.0
