"""Benchmark harness entry point: one experiment per paper table/figure,
plus beyond-paper studies.  ``python -m benchmarks.run [--frames N] [names...]``

``--frames N`` forwards a small frame count to every suite that accepts
one — the CI smoke job uses it to catch benchmark bit-rot in seconds
instead of minutes.

Prints ``CSV,name,us_per_call,derived`` lines for machine consumption and
writes JSON artifacts under artifacts/bench/.
"""

from __future__ import annotations

import importlib
import inspect
import sys
import time

#: suite name -> module under benchmarks/ (imported lazily so the
#: stdlib-only suites run without jax — the CI smoke leg has none)
SUITES = {
    "fig2": "fig2_resnet8",
    "fig3": "fig3_resnet18",
    "table1": "table1_utilization",
    "fig4": "fig4_imc_dpu",
    "yolo": "yolo_latency",
    "quality": "scheduler_quality",
    "elastic": "elastic_bench",
    "multi_tenant": "multi_tenant",
    "replication": "replication",
    "serving": "serving",
    "sensitivity": "sensitivity",
    "partition": "lm_partition",
}


def main() -> None:
    args = sys.argv[1:]
    frames = None
    if "--frames" in args:
        i = args.index("--frames")
        try:
            frames = int(args[i + 1])
        except (IndexError, ValueError):
            print("usage: python -m benchmarks.run [--frames N] [names...]")
            raise SystemExit(2)
        del args[i : i + 2]
    want = args or list(SUITES)
    t0 = time.time()
    for name in want:
        if name not in SUITES:
            print(f"unknown suite '{name}'; have {sorted(SUITES)}")
            continue
        module = importlib.import_module(f".{SUITES[name]}", package=__package__)
        fn = module.main
        kw = {}
        if frames is not None and "frames" in inspect.signature(fn).parameters:
            kw["frames"] = frames
        print(f"\n######## {name} ########")
        t1 = time.time()
        fn(**kw)
        print(f"[{name} done in {time.time()-t1:.1f}s]")
    print(f"\nall benchmarks done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
