"""Offline traffic (MLPerf Offline): a closed loop over frames in host memory.

The mix gives ``batch`` (frames a call) and ``outstanding`` (calls in
flight).  Call k + 1 is dispatched before call k's logits are fetched, up
to ``outstanding`` calls in flight, until the window closes; calls still in
flight then are fetched after it.  Only answers that reached the host
inside the window count, and only those are compared.
"""

from __future__ import annotations

from collections import deque
from typing import Dict

from bench import loops

ANSWERS_AFTER_CLOSE_COUNT = False


def validate(mix: Dict) -> None:
    if mix["batch"] < 1 or mix["outstanding"] < 1:
        raise ValueError("batch and outstanding must be at least 1")


def window(dep, seconds: float, rec: loops.Record, rate=None) -> loops.Record:
    mix = dep.cell.mix
    batch, pool = mix["batch"], dep.pool
    n_batches = len(pool) // batch
    pending: deque = deque()
    rec.t0 = loops.now()
    rec.end = rec.t0 + seconds
    k = 0
    while loops.now() < rec.end:
        rec.step_profiler()
        start = (k % n_batches) * batch
        call = {"index": k, "first": start, "n": batch}
        out = rec.dispatch(dep.serve, call, pool[start:start + batch])
        k += 1
        if out is None:
            rec.fail(call)
            continue
        pending.append((call, out))
        if len(pending) >= mix["outstanding"]:
            rec.finish(*pending.popleft())
    while pending:
        rec.finish(*pending.popleft())
    return rec
