"""The comparison that decides ``correct``.

The answers compared are logits that the timed window fetched to the host.
The reference is the benchmark's own float32 network at full precision, on
the same frames and weights.  With seeded weights most of a logit vector is
the same for every frame, so differences are measured against the part
that depends on the frame: the reference's logits less their mean over the
sample.  Two numbers are compared, each with its limit from the
configuration's file:

* ``rel_l2``: the L2 norm of all differences over that of the reference's
  centred logits, over the whole sample;
* ``worst_frame``: the largest L2 norm of one frame's differences, over the
  root mean square of the reference's centred per-frame norms.  It catches
  one answer gone wrong among many right ones.

A frame that raised, came back with the wrong shape or not finite, or never
came back counts in ``failed``, whose limit is 0.
"""

from __future__ import annotations

from typing import Callable, Dict

import numpy as np

BLOCK = 256     # frames per reference call, so that it compiles once


def numbers(got: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return {"rel_l2": float("inf"), "worst_frame": float("inf")}
    diff = np.linalg.norm(got - want, axis=1)
    norm = np.linalg.norm(want - want.mean(axis=0), axis=1)
    return {"rel_l2": float(np.sqrt((diff ** 2).sum() / (norm ** 2).sum())),
            "worst_frame": float(diff.max() / np.sqrt((norm ** 2).mean()))}


def in_blocks(fn: Callable, frames: np.ndarray) -> np.ndarray:
    """``fn`` over ``frames`` in blocks of ``BLOCK``, the last one padded."""
    out = []
    for i in range(0, len(frames), BLOCK):
        block = frames[i:i + BLOCK]
        n = len(block)
        if n < BLOCK:
            block = np.concatenate(
                [block, np.zeros((BLOCK - n,) + block.shape[1:], block.dtype)])
        out.append(np.asarray(fn(block))[:n])
    return np.concatenate(out)


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """``{name: {"value", "limit"}}`` and whether every value is within its
    limit.  A value that is missing (nothing was answered) or not finite is
    not, and is written as None, since JSON has no infinity."""
    table = {}
    for name, limit in limits.items():
        v = values.get(name)
        table[name] = {"value": v if v is not None and np.isfinite(v) else None,
                       "limit": limit}
    ok = all(v["value"] is not None and v["value"] <= v["limit"]
             for v in table.values())
    return {"numbers": table, "correct": ok}
