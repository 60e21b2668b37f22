"""Profile one cell with the program's own spans on, and print what they show.

    python3 bench/profile_program.py --workload resnet8.offline --seed 1 \\
        --seconds 51 [--pairs 2 --pair-seconds 15] [--out DIR --name NAME]

Run from the root of a checkout on the chip.  After the cell's set-up it
runs:

* with ``--pairs N``: N pairs of unprofiled windows of ``--pair-seconds``,
  program recording off then on then on then off..., each reporting
  ``dispatch_ms`` (mean host time of a ``serve`` call) and the full passes
  of Python's collector: what recording costs;
* one window of ``--seconds`` with recording on throughout and the
  profiler over ``--trace-seconds`` of its middle, as a ``--trace 1`` run
  of ``bench/run.py`` profiles it.

Of that window it prints, as one JSON line: ``xplane.summarize``'s keys,
``program_trace.summarize``'s (launches and device time by program span),
the share of ``execute`` time spent in each phase outside the profiled
part (host clock), the counters per call, and ``slowest_span``: the
longest span inside a call over 10 ms with no child over 10 ms (else the
longest span), with such spans over 100 ms in ``held``.  With ``--out`` the trace is copied to
``<out>/<name>.xplane.pb``; ``--traffic`` replaces the cell's traffic mix
(the trace that ``bench/tests/test_program_trace.py`` reads is a second of
``resnet8.offline`` under ``stream_resnet8``, seed 7, 0.5 s profiled).
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

HELD_S = 0.1            # a span this long is reported under ``held``
INNERMOST_OVER_S = 0.01


def dispatch_ms(cell, rec) -> Optional[float]:
    """``dispatch_ms`` as the benchmark reads it: the mean host time of a
    ``serve`` call, outside the profiled part of the window."""
    from bench import harness
    return harness.reader("dispatch_ms")(harness.Run(cell, rec, 0.0, {}, None))


def phase_shares(rows: Dict, names, keep) -> Dict[str, float]:
    """Per span name, % of the ``execute`` spans' wall time spent in spans
    of that name (their self time for ``execute`` and ``node``), over
    the rows selected by ``keep``."""
    code = {n: i for i, n in enumerate(names)}
    total = rows["dur"][keep & (rows["name"] == code["execute"])].sum()
    if total <= 0:
        return {}
    out = {}
    for n, i in code.items():
        sel = keep & (rows["name"] == i)
        col = rows["self"] if n in ("execute", "node") else rows["dur"]
        out[n] = 100.0 * col[sel].sum() / total
    return out


def slowest(rec, rows: Dict, names, t0: float) -> Dict:
    """Where a call was held: the longest span inside a call that lasted
    over ``INNERMOST_OVER_S`` and has no child that did (else the longest
    span), and every such span over ``HELD_S``, each with the wall and
    thread-CPU seconds of its call.  A call itself never counts: a
    ResNet-18 call lasts 0.15 s spread over 150 short spans."""
    if not len(rows["id"]):
        return {}
    long = rows["dur"] > INNERMOST_OVER_S
    has_long_child = np.zeros(len(long), bool)
    has_long_child[rows["parent"][long & (rows["parent"] >= 0)]] = True
    inner = long & ~has_long_child & (rows["parent"] >= 0)

    def row(i):
        call = rows["call"][i]
        return {"name": names[rows["name"][i]], "node": rec.label(rows["node"][i]),
                "at_s": float(rows["t0"][i] - t0), "wall_s": float(rows["dur"][i]),
                "call_wall_s": float(rows["dur"][call]) if call >= 0 else None,
                "call_cpu_s": float(rows["cpu"][call]) if call >= 0 else None}

    pick = np.flatnonzero(inner) if inner.any() else rows["id"]
    top = pick[np.argmax(rows["dur"][pick])]
    held = np.flatnonzero(inner & (rows["dur"] > HELD_S))
    return {"slowest_span": row(top), "held": [row(i) for i in held]}


def profile(cell, seed: int, seconds: float, trace_seconds: float,
            pairs: int, pair_seconds: float,
            out: Optional[Path] = None, name: str = "") -> Dict:
    from bench import harness, loops, program_trace, xplane
    from repro import obs

    dep = harness.Deployment(cell, seed)
    result: Dict = {"workload": cell.name, "seed": seed,
                    "setup_phases_s": dict(dep.phases), "pairs": []}
    for k in range(2 * pairs):
        on = k % 4 in (1, 2)
        with harness.watch() as w:
            if on:
                with obs.recording():
                    rec = harness.window(dep, pair_seconds)
            else:
                rec = harness.window(dep, pair_seconds)
        health = harness.window_health(rec, w)
        result["pairs"].append({"recording": on,
                                "dispatch_ms": dispatch_ms(cell, rec),
                                "calls": len(rec.calls),
                                "full_gc": health["full_gc"]})

    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        length = min(trace_seconds, seconds / 2)
        profiler = loops.Profiler(tmp, (seconds - length) / 2, length)
        with harness.watch() as w, obs.recording() as orec:
            rec = harness.window(dep, seconds, profiler)
        files = sorted(Path(tmp).rglob("*.xplane.pb"))
        pd = xplane.read(files[-1]) if files else None
        if out is not None and files:
            out.mkdir(parents=True, exist_ok=True)
            shutil.copy(files[-1], out / f"{name}.xplane.pb")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    rows = orec.rows()
    skip = (profiler.on, profiler.off) if profiler.on is not None else None
    untraced = np.ones(len(rows["id"]), bool)
    if skip:
        untraced = ~((rows["t0"] >= skip[0]) & (rows["t0"] <= skip[1]))
    calls = int((rows["name"] == 0).sum())
    result.update({
        "window": harness.window_health(rec, w),
        "dispatch_ms_untraced": dispatch_ms(cell, rec),
        "execute_spans": calls,
        "per_call": {k: v / max(calls, 1) for k, v in orec.counters.items()},
        "host_share_untraced": phase_shares(rows, obs.SPAN_NAMES, untraced),
        "host_share_traced": phase_shares(rows, obs.SPAN_NAMES, ~untraced),
        "execute_ms": {
            "median": float(np.median(rows["dur"][rows["name"] == 0]) * 1e3)
            if calls else None},
    })
    result.update(slowest(orec, rows, obs.SPAN_NAMES, rec.t0))
    if pd is not None:
        result["trace"] = xplane.summarize(pd)
        result["program_trace"] = program_trace.summarize(pd, obs.SPAN_NAMES)
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace-seconds", type=float, default=4.0)
    ap.add_argument("--pairs", type=int, default=0)
    ap.add_argument("--pair-seconds", type=float, default=15.0)
    ap.add_argument("--traffic")
    ap.add_argument("--out")
    ap.add_argument("--name")
    args = ap.parse_args(argv)

    from bench import harness, traffic
    from bench.run import NoAccelerator, accelerator, use_compile_cache
    cell = harness.Cell(args.workload)
    if args.traffic:
        cell.mix = traffic.load(ROOT / "bench" / "traffic" / f"{args.traffic}.json")
        cell.kind = traffic.kind(cell.mix["kind"])
    use_compile_cache()
    try:
        accelerator(cell.chips)
    except NoAccelerator as e:
        print(f"profile_program: {e}", file=sys.stderr)
        return 2
    out = Path(args.out) if args.out else None
    result = profile(cell, args.seed, args.seconds, args.trace_seconds,
                     args.pairs, args.pair_seconds, out,
                     args.name or args.workload)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
