"""Record a few steps of one cell under the profiler, show the trace's layout,
and keep a short stretch of it as the JSON the trace-reduction tests read.

    python3 benchmarks/chip/record_trace.py --workload qwen05b-short128 --seed 7 \
        --steps 12 --keep-ms 400 --out benchmarks/chip/tests/data/trace_short128.json

Set-up and the first steps are the benchmark's own; then ``--steps`` steps of
the trainer's loop body run under ``jax.profiler`` with the harness's host
spans.  Prints every plane of the ``.xplane.pb`` with its lines, their event
counts and a few event names, then what ``trace_reduce.load`` read from it.
``--out`` gets the ``Trace`` clipped to the last ``--keep-ms`` milliseconds
before the final step's loss fetch ended, with the window span kept.
"""

from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import harness  # noqa: E402
import trace_reduce as tr  # noqa: E402


def show_planes(log_dir: Path) -> None:
    from jax.profiler import ProfileData
    f = sorted(log_dir.rglob("*.xplane.pb"))[-1]
    print(f"{f.name}: {f.stat().st_size} bytes")
    for plane in ProfileData.from_file(str(f)).planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines[:12]:
            events = list(line.events)
            names = sorted({e.name for e in events[:200]})[:4]
            print(f"  line {line.name!r}: {len(events)} events, e.g. {names}")


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--keep-ms", type=float, default=400.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    harness.enable_cache()
    cell = harness.load_cell(args.workload)
    devices = harness.chips(cell.chips)
    work = Path(tempfile.mkdtemp(prefix="hoard-trace-"))
    try:
        prog = harness.Program(cell, devices)
        run = harness.Run(prog, args.seed, work / "stripes")
        harness.warm_readings(run)
        run.step()["loss"].block_until_ready()
        jax.profiler.start_trace(str(work / "trace"), profiler_options=harness.profile_options())
        run.spans.annotate = True
        with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN):
            last = None
            for _ in range(args.steps):
                last = run.step()
            float(last["loss"])
        jax.profiler.stop_trace()
        show_planes(work / "trace")
        t = tr.load(str(work / "trace"), set(harness.SPANS) | {harness.WINDOW_SPAN})
        for d, ops in sorted(t.ops.items()):
            print(f"device {d}: {len(ops)} ops from {ops[0][1]} to {ops[-1][2]}")
        names = sorted({s[0] for s in t.spans})
        print(f"host spans: {len(t.spans)} of {names}")
        _, lo, hi = [s for s in t.spans if s[0] == harness.WINDOW_SPAN][-1]
        red = tr.reduce(t, lo, hi, sorted(t.ops)[:len(devices)])
        print(f"window {red.window_s:.6f} s, busy {red.busy_s}, idle share {red.idle_share:.4f}")
        print(f"top ops {red.top_ops}")
        print(f"idle gaps {red.idle_by_span}")
        if args.out:
            cut = hi - int(args.keep_ms * 1e6)
            kept = tr.Trace(
                {d: [e for e in ops if e[2] > cut and e[1] < hi] for d, ops in t.ops.items()
                 if d in sorted(t.ops)[:len(devices)]},
                [(n, max(a, cut), b) for n, a, b in t.spans if b > cut and a < hi])
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.out).write_text(kept.to_json())
            print(f"kept {sum(map(len, kept.ops.values()))} ops, {len(kept.spans)} spans "
                  f"in {args.out}")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
