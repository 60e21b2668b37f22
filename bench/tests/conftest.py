"""CPU rehearsal of the benchmark: ``python -m pytest bench/tests`` from the
root of the repository.  These tests never need a chip."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def small_cell(name, frames=16, seconds_rate=20.0):
    """The cell ``name`` at a size the CPU runs in seconds: a batch of 4
    for offline mixes, ``frames`` in the pool and all of them compared, a
    calibration batch of 8."""
    from bench import harness
    cell = harness.Cell(name)
    mix = dict(cell.mix, pool_frames=frames, sample_frames=4 * frames)
    if mix["kind"] == "offline":
        mix["batch"] = 4
    else:
        mix["rate_fps"] = seconds_rate
    cell.mix = mix
    cell.cfg = dict(cell.cfg, deployment=dict(cell.cfg["deployment"],
                                              calibration_frames=8))
    return cell


def cpu_run(cell, seed=5, seconds=1.0, traced=False):
    import jax

    from bench import harness
    peaks = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
    return harness.run_cell(cell, seed, seconds, traced, 0.0, jax.devices(),
                            peaks)
