"""The comparison that decides ``correct``.

The answers compared are what the timed window fetched to the host: one
array per frame (logits, or a detection head's rows).  The reference is the
benchmark's own float32 network at full precision, on the same frames and
weights.  With seeded weights most of an answer is the same for every
frame, so differences are measured against the part that depends on the
frame: the reference's answers less their mean over the sample.  Two
numbers are compared, each with its limit from the configuration's file:

* ``rel_l2``: the L2 norm of all differences over that of the reference's
  centred answers, over the whole sample;
* ``worst_frame``: the largest L2 norm of one frame's differences, over the
  root mean square of the reference's centred per-frame norms.  It catches
  one answer gone wrong among many right ones.

A configuration may declare ``check.parts``: part name -> ``[start, stop)``
on the last axis of an answer, such as a detector's box numbers and class
scores.  Each part is then judged alone, centred on its own reference mean,
as ``<part>.rel_l2`` and ``<part>.worst_frame``: a part that carries a
small share of the answer's energy cannot hide inside the whole.  Its
``limits`` then name exactly those numbers.

A frame that raised, came back with the wrong shape or not finite, or never
came back counts in ``failed``, whose limit is 0.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

BLOCK = 256     # frames per reference call, so that it compiles once
NUMBERS = ("rel_l2", "worst_frame")

Parts = Optional[Dict[str, Tuple[int, int]]]


def names(parts: Parts) -> List[str]:
    """The numbers compared: ``NUMBERS``, or each of them for each part."""
    if not parts:
        return list(NUMBERS)
    return [f"{part}.{n}" for part in parts for n in NUMBERS]


def validate(spec: Dict) -> None:
    """Raise ``ValueError`` unless ``spec`` (a configuration's ``check``)
    gives a limit to exactly the numbers its ``parts`` make."""
    parts = spec.get("parts")
    for part, span in (parts or {}).items():
        if not (isinstance(span, list) and len(span) == 2
                and all(isinstance(i, int) for i in span)
                and 0 <= span[0] < span[1]):
            raise ValueError(f"check.parts.{part} is not [start, stop) with "
                             f"0 <= start < stop: {span!r}")
    want, have = set(names(parts)), set(spec["limits"])
    if want != have:
        raise ValueError(
            f"check.limits must name exactly {sorted(want)}: without a "
            f"limit {sorted(want - have)}, without a number "
            f"{sorted(have - want)}")


def _numbers(got: np.ndarray, want: np.ndarray) -> Tuple[float, float]:
    """``rel_l2`` and ``worst_frame`` of answers given as (frames, -1)."""
    diff = np.linalg.norm(got - want, axis=1)
    norm = np.linalg.norm(want - want.mean(axis=0), axis=1)
    return (float(np.sqrt((diff ** 2).sum() / (norm ** 2).sum())),
            float(diff.max() / np.sqrt((norm ** 2).mean())))


def numbers(got: np.ndarray, want: np.ndarray,
            parts: Parts = None) -> Dict[str, float]:
    """The numbers of ``names(parts)`` for answers of shape (frames, ...)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return {n: float("inf") for n in names(parts)}
    frames = len(want)
    if not parts:
        return dict(zip(NUMBERS, _numbers(got.reshape(frames, -1),
                                          want.reshape(frames, -1))))
    out = {}
    for part, (start, stop) in parts.items():
        if stop > want.shape[-1]:
            raise ValueError(f"check.parts.{part} ends at {stop}, past the "
                             f"answer's last axis of {want.shape[-1]}")
        values = _numbers(got[..., start:stop].reshape(frames, -1),
                          want[..., start:stop].reshape(frames, -1))
        out.update({f"{part}.{n}": v for n, v in zip(NUMBERS, values)})
    return out


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
