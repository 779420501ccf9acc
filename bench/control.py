#!/usr/bin/env python3
"""Read a cell's compared numbers for the program, for its control and for
the program with a fault planted, on several seeds in one process (the
chip's compiles are shared).

    python3 bench/control.py --workload ast-esc50-serve --seconds 3 \\
        --seeds 11 12 13
    python3 bench/control.py --workload ast-esc50-serve --seconds 3 \\
        --seeds 11 12 13 --fault serve_altered_answer

The control is the plain reference put in the program's place one
precision step below what the configuration states (float8 weights for a
bfloat16 backbone; three bfloat16 passes for float32 at ``highest``),
judged by the same comparison.  ``--fault`` plants one of
``bench/faults.py``'s faults in the program before set-up and reads the
program alone.  Each seed prints one JSON line with each side's numbers and
whether the cell's limits, applied as ``run.py`` applies them, find it
correct; a limit lies between the largest the program gives and the
smallest the control (or a fault) gives.  The benchmark's own runs never
run the control or a fault.
"""

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None,
                    help="plant this fault of bench/faults.py first")
    args = ap.parse_args()
    from bench.lib import env
    env.prepare(ROOT)
    env.check_device(1)
    from bench import faults, run
    from bench.lib import counts
    ld = run.load_cell(args.workload)
    kind = ld["traffic"]["kind"]
    if args.fault is not None:
        fault_kind, plant = faults.FAULTS[args.fault]
        if fault_kind != kind:
            raise SystemExit(f"{args.fault} is a fault of {fault_kind} cells")
        plant(setattr)
    limits = ld["workload"]["limits"]
    drv = importlib.import_module(f"bench.drivers.{kind}")
    for seed in args.seeds:
        t0 = time.perf_counter()
        ctx = run.Context(name=args.workload, seed=seed,
                          seconds=args.seconds, spans=env.Spans(),
                          config=ld["config"], traffic=ld["traffic"],
                          workload=ld["workload"], counts=counts)
        cell = drv.Cell(ctx)
        cell.setup()
        cell.window(args.seconds)
        cell.metrics()
        cell.release()
        prog = cell.check()
        out = {"seed": seed, "fault": args.fault, "program": prog,
               "program_correct": run.judge(prog, limits)}
        if args.fault is None:
            ctl = cell.control()
            out.update(control=ctl, control_correct=run.judge(ctl, limits))
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
