"""Readings that the correctness limits are set from, on the chip.

    python3 bench/control.py --workload resnet18_cifar.offline \\
        --seeds 1,2,3 --seconds 5

For each seed, in one process: the cell's set-up, a window of ``--seconds``
at the cell's own load, and the comparison of a run (``harness.compare``).
Beside the program's numbers it prints the control's: the reference
computed at the precision below the configuration's (int4 weights and
activations for an int8 deployment, ``check.control_qmax``) on the same
sampled frames.  A limit belongs above every program reading and below
every control reading.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.run import NoAccelerator, accelerator, use_compile_cache
    cell = harness.Cell(args.workload)
    use_compile_cache()
    try:
        accelerator(cell.chips)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        dep = harness.Deployment(cell, seed)
        rec = harness.window(dep, args.seconds)
        dep.serve = None
        cmp = harness.compare(dep, rec, control=True)
        row = {"seed": seed, "frames": cmp["frames"],
               "program": cmp["numbers"], "control": cmp["control"],
               "errors": len(rec.errors), "seconds": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    names = rows[0]["program"].keys()
    out = {"workload": cell.name,
           "program_max": {k: max(r["program"][k] for r in rows) for k in names},
           "control_min": {k: min(r["control"][k] for r in rows) for k in names},
           "limits": cell.cfg["check"]["limits"], "runs": rows}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
