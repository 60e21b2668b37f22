"""The one traffic generator: frames and arrival times from a mix's file.

A mix is a JSON file under ``bench/traffic``.  Its ``kind`` names the loop
that drives the window, ``bench/kinds/<kind>.py`` (``offline``: a closed
loop of ``batch`` frames a call; ``stream``: an open loop of single frames
at ``rate_fps``), which also checks the mix's own parameters.  Every mix
draws ``pool_frames`` seeded frames that the window cycles through, and
compares ``sample_frames`` of the answers with the reference.

Every seed gets the same amount of work: the same number of frames, and
for a stream the same arrivals.  A stream's arrival order is drawn from the
mix's own ``schedule_seed``, not from the run's seed: in a queue at four
fifths of its capacity, a tail latency over a few hundred frames depends
more on the order of the gaps than on anything the program does.
"""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path
from typing import Dict, Optional

import numpy as np

KINDS_DIR = Path(__file__).resolve().parent / "kinds"


def kind(name: str):
    """The module ``bench/kinds/<name>.py`` that drives a mix's window."""
    if not re.fullmatch(r"[a-z][a-z0-9_]*", str(name)) \
            or not (KINDS_DIR / f"{name}.py").exists():
        known = sorted(p.stem for p in KINDS_DIR.glob("[a-z]*.py"))
        raise ValueError(f"no traffic kind {name!r} in bench/kinds ({known})")
    return importlib.import_module(f"bench.kinds.{name}")


def load(path: Path) -> Dict:
    mix = json.loads(Path(path).read_text())
    try:
        kind(mix.get("kind")).validate(mix)
        if mix["pool_frames"] % mix["batch"]:
            raise ValueError("pool_frames is not a multiple of batch")
    except (KeyError, ValueError) as e:
        raise ValueError(f"{path}: {e}") from None
    return mix


def rngs(seed: int) -> Dict[str, np.random.Generator]:
    """Independent generators for each use of the seed (any whole number)."""
    names = ("weights", "calibration", "frames", "arrivals", "sample")
    kids = np.random.SeedSequence(seed % 2**64).spawn(len(names))
    return {n: np.random.default_rng(k) for n, k in zip(names, kids)}


def frames(rng: np.random.Generator, n: int, hw) -> np.ndarray:
    """``n`` NHWC frames of standard normal pixels, float32, on the host."""
    return rng.standard_normal((n, hw[0], hw[1], 3), dtype=np.float32)


def gaps(rate: float, seconds: float) -> np.ndarray:
    """The ``round(rate * seconds)`` gaps between Poisson arrivals: the
    exponential distribution's quantiles at ``(i + 0.5) / n``, scaled to a
    mean of exactly ``1 / rate``."""
    n = max(1, round(rate * seconds))
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g * ((n / rate) / g.sum())


def arrivals(rng: np.random.Generator, rate: float, seconds: float,
             bursts: Optional[Dict] = None) -> np.ndarray:
    """Offsets in seconds from the window's start of ``round(rate *
    seconds)`` arrivals: the first at 0, then ``gaps`` in the order
    ``rng`` shuffles them into.

    With ``bursts`` (``period_s``, ``on_share``), arrivals come only in the
    first ``on_share`` of each period, at ``rate / on_share``: the same
    arrivals at the same mean rate, packed into on-phases.
    """
    share = 1.0 if bursts is None else bursts["on_share"]
    g = gaps(rate / share, seconds * share)
    rng.shuffle(g)
    on = np.concatenate([[0.0], np.cumsum(g[:-1])])
    if bursts is None:
        return on
    on_s = bursts["period_s"] * share
    return np.floor(on / on_s) * bursts["period_s"] + np.mod(on, on_s)
