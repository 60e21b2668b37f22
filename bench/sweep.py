"""Find a stream cell's offered rate on the chip: half of the capacity.

    python3 bench/sweep.py --workload resnet8.stream --seed 1 --seconds 20 \\
        [--fractions 0.6,0.8,1.0]

Set-up is the cell's own.  The capacity is the rate at which one server
answers frames back to back, measured closed loop for ``--seconds``; with
single frames served first in, first out, no higher offered rate can be
sustained.  The cell's ``rate_fps`` is half of it, rounded to 0.1.  With
``--fractions``, the mix's arrival schedule is also offered at those
fractions of the capacity, ``--seconds`` each, and the latencies are
printed as a report; they decide nothing.  Prints one JSON line; writing
the rate into the traffic file is left to the reader.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

LOAD = 0.5          # the cell's rate as a share of the capacity (see PERF.md)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fractions", default="",
                    help="comma-separated shares of the capacity to report")
    args = ap.parse_args(argv)

    import numpy as np

    from bench import harness, loops
    from bench.run import NoAccelerator, accelerator, use_compile_cache
    cell = harness.Cell(args.workload)
    if cell.mix["kind"] != "stream":
        print("bench: the sweep is for stream cells", file=sys.stderr)
        return 2
    use_compile_cache()
    try:
        accelerator(cell.chips)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    dep = harness.Deployment(cell, args.seed)
    t0, n = loops.now(), 0
    while loops.now() < t0 + args.seconds:
        np.asarray(dep.serve(dep.pool[n % len(dep.pool)][None]))
        n += 1
    capacity = n / (loops.now() - t0)
    points = []
    for frac in [float(f) for f in args.fractions.split(",") if f]:
        rec = harness.window(dep, args.seconds, rate=frac * capacity)
        lat = np.array([c["done"] - c["due"] for c in rec.calls])
        points.append({"offered_fps": frac * capacity, "frames": len(lat),
                       "p50_ms": float(np.percentile(lat, 50)) * 1e3,
                       "p90_ms": float(np.percentile(lat, 90)) * 1e3})
        print(json.dumps(points[-1]), file=sys.stderr, flush=True)
    out = {"workload": cell.name, "capacity_fps": capacity,
           "rate_fps": round(LOAD * capacity, 1), "points": points}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
