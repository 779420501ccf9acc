#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: one process, one set-up,
then a short window at each offered rate.

    python3 bench/sweep.py --workload ast-esc50-serve --seed 1 \\
        --seconds 5 --rates 60 100 140 180

For each rate it prints the offered and retired rates, the p50 and p95
latency from due time, the requests shed, the share of the offered requests
retired by the window's close and the seconds the backlog took to drain
after it.  A rate is sustained where that share is at least 0.99 and the
drain at most 0.25 s: the backlog did not grow through the window.  The
knee is the highest sustained rate below the lowest one that is not; a
cell's ``rate`` is set at four fifths of it.  The benchmark's own runs never
search: they offer the cell's fixed rate.
"""

KEPT_UP = 0.99        # share of the offered requests retired by the close
MAX_DRAIN_S = 0.25    # seconds the backlog may take to drain after it

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    from bench.lib import env
    env.prepare(ROOT)
    env.check_device(1)
    import importlib
    import numpy as np
    from bench import run
    from bench.lib import counts
    ld = run.load_cell(args.workload)
    ctx = run.Context(name=args.workload, seed=args.seed,
                      seconds=args.seconds, spans=env.Spans(),
                      config=ld["config"], traffic=ld["traffic"],
                      workload=ld["workload"], counts=counts)
    cell = importlib.import_module(
        f"bench.drivers.{ld['traffic']['kind']}").Cell(ctx)
    cell.setup()
    print(f"setup_s={time.perf_counter() - T_START:.3f}", flush=True)
    knee = None
    for rate in sorted(args.rates):
        cell.new_session(rate)
        cell.window(args.seconds)
        rep = cell.session.report()
        lat = np.array(list(cell.latency.values())) * 1e3
        done_at = [cell.due_of[r] + v
                   for r, v in cell.latency.items()]
        by_close = sum(t <= args.seconds for t in done_at) / cell.attempted
        drain = cell.loop_s - args.seconds
        sustained = bool(by_close >= KEPT_UP and drain <= MAX_DRAIN_S)
        print(json.dumps({
            "rate": rate, "attempted": cell.attempted,
            "retired_per_s": len(lat) / cell.loop_s,
            "retired_by_close": float(by_close), "drain_s": drain,
            "sustained": sustained, "shed": int(rep.shed),
            "hit_ratio": rep.hit_ratio,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "backbone_calls": len(cell.adapter.calls)}), flush=True)
        if not sustained:
            break
        knee = rate
    print(json.dumps({"knee": knee, "rate_at_0.8": knee and 0.8 * knee,
                      "bracketed": not sustained}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
