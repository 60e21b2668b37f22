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


def small_cell(name, frames=16, seconds_rate=20.0, root=ROOT):
    """The cell ``name`` at a size the CPU runs in seconds: a batch of 4
    for offline mixes, ``frames`` in the pool and all of them compared, a
    calibration batch of 8."""
    from bench import harness
    cell = harness.Cell(name, root)
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


# resnet8's 10 logits judged as two parts, the second of one logit: as a
# detector's class scores are to its boxes, a part with a small share of
# the answer's energy
PARTS = {"head": [0, 9], "last": [9, 10]}
PART_LIMITS = {"head.rel_l2": 0.8, "head.worst_frame": 1.4,
               "last.rel_l2": 0.8, "last.worst_frame": 1.4}


def parts_root(tmp_path, parts=PARTS, limits=PART_LIMITS):
    """A root under ``tmp_path`` whose ``BENCHMARK.json`` is the repo's and
    whose ``resnet8`` configuration is the repo's with ``check.parts`` and
    ``check.limits`` replaced."""
    import json
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    entry = [c for c in spec["configs"] if c["name"] == "resnet8"][0]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    cfg["check"] = dict(cfg["check"], parts=parts, limits=limits)
    path = tmp_path / entry["file"]
    path.parent.mkdir(parents=True)
    path.write_text(json.dumps(cfg))
    return tmp_path
