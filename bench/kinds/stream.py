"""Stream traffic (MLPerf SingleStream/Server): an open loop of single frames.

The mix gives ``rate_fps``, the mean offered rate, and ``schedule_seed``,
which orders the arrivals (``traffic.arrivals``: the same gaps for every
run seed); an optional ``bursts`` object (``period_s``, ``on_share``)
packs them into on-phases at the same mean rate.  Frames are served first
in, first out, one call a frame, each as soon as it is due and the one
before it is answered.  Frames still waiting when the window closes are
served late, up to ``GRACE_S`` past the close, with the wait in their
latency; any left then are failed.  Every answered frame is compared.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import numpy as np

from bench import loops, traffic

ANSWERS_AFTER_CLOSE_COUNT = True
GRACE_S = 60.0


def validate(mix: Dict) -> None:
    if mix["batch"] != 1 or not mix["rate_fps"] > 0:
        raise ValueError("a stream serves single frames at a positive rate")
    if not isinstance(mix["schedule_seed"], int):
        raise ValueError("schedule_seed must be a whole number")
    bursts = mix.get("bursts")
    if bursts is not None and not (bursts["period_s"] > 0
                                   and 0 < bursts["on_share"] <= 1):
        raise ValueError("bursts needs period_s > 0 and 0 < on_share <= 1")


def offsets(mix: Dict, seconds: float, rate: Optional[float] = None) -> np.ndarray:
    """Arrival offsets from the window's start: the mix's own schedule."""
    rng = np.random.default_rng(mix["schedule_seed"])
    return traffic.arrivals(rng, rate or mix["rate_fps"], seconds,
                            mix.get("bursts"))


def sleep_until(t: float) -> None:
    """Sleep, then spin for the last half millisecond."""
    left = t - loops.now()
    if left > 6e-4:
        time.sleep(left - 5e-4)
    while loops.now() < t:
        pass


def window(dep, seconds: float, rec: loops.Record,
           rate: Optional[float] = None) -> loops.Record:
    pool = dep.pool
    rec.t0 = loops.now() + 1e-3
    rec.end = rec.t0 + seconds
    for i, off in enumerate(offsets(dep.cell.mix, seconds, rate)):
        due = rec.t0 + off
        first = i % len(pool)
        call = {"index": i, "first": first, "n": 1, "due": due}
        if loops.now() > rec.end + GRACE_S:
            rec.calls.append(call)
            rec.fail(call)
            continue
        rec.step_profiler()
        if loops.now() < due:
            with rec.spans("wait_arrival"):
                sleep_until(due)
        out = rec.dispatch(dep.serve, call, pool[first:first + 1])
        if out is None:
            rec.fail(call)
            continue
        rec.finish(call, out)
    return rec
