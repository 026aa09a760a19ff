"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 benchmarks/chip/calibrate.py --workload qwen05b-short128 \
        --seeds 11 12 13 ... [--control-seeds 3] [--fault half_batch] [--out FILE]

For each seed, in one process on the chip and at the cell's own sizes:

* the program: the first ``reference_steps`` steps through the window's own
  call and feed, compared with the float32 reference (the lower reading);
* the control (first ``--control-seeds`` seeds): the reference itself, its
  matrix products in float8, put in the program's place (an upper reading);
* each ``--fault``, planted in the program, on the first ``--control-seeds``
  seeds (upper readings for the faults the cell can have).

No window is measured.  Prints one JSON line per reading, and writes them to
``--out`` too.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import harness  # noqa: E402


def _line(out, **row):
    s = json.dumps(row)
    print(s, flush=True)
    if out:
        with open(out, "a") as fh:
            fh.write(s + "\n")


def _numbers(r: dict) -> dict:
    return {k: r[k] for k in ("loss_gap", "grad_gap", "grad_leaf", "change_gap", "change_leaf")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    harness.enable_cache()
    cell = harness.load_cell(args.workload)
    devices = harness.chips(cell.chips)
    work = Path(tempfile.mkdtemp(prefix="hoard-calib-"))
    try:
        for fault in [None] + args.fault:
            prog = harness.Program(cell, devices, fault)
            seeds = args.seeds if fault is None else args.seeds[:args.control_seeds]
            for i, seed in enumerate(seeds):
                t0 = time.perf_counter()
                run = harness.Run(prog, seed, work / f"{fault}-{seed}")
                warm = harness.warm_readings(run)
                wrong = harness.rows_wrong(run)
                run.free()
                shutil.rmtree(work / f"{fault}-{seed}", ignore_errors=True)
                ref = harness.reference_readings(prog, seed)
                got = harness.compare(warm, ref)
                _line(args.out, workload=cell.name, seed=seed, side=fault or "program",
                      rows_wrong=wrong, **_numbers(got), losses=warm["losses"],
                      ref_losses=ref["losses"], left_out=got["leaves_left_out"],
                      seconds=time.perf_counter() - t0)
                if fault is None and i < args.control_seeds:
                    low = harness.reference_readings(prog, seed, low=True)
                    ctl = harness.compare(low, ref)
                    _line(args.out, workload=cell.name, seed=seed, side="control_fp8",
                          **_numbers(ctl), losses=low["losses"])
            del prog
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
