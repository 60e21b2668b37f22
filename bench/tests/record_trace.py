"""Record the small chip trace that ``test_xplane.py`` reads.

    python3 bench/tests/record_trace.py --out <directory>

Runs ResNet-8 under the ``stream_resnet8`` mix (single frames, Poisson) on
the chip: set-up, then half a second of its window profiled with the
harness's own profiler settings.  It copies the ``.xplane.pb`` to
``<out>/resnet8_stream.xplane.pb``.  The checked-in copy under
``bench/tests/data`` came from a TPU v5 lite.
"""

from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from bench import harness, loops, traffic
    from bench.run import accelerator, use_compile_cache
    cell = harness.Cell("resnet8.offline")
    cell.mix = traffic.load(ROOT / "bench" / "traffic" / "stream_resnet8.json")
    cell.kind = traffic.kind(cell.mix["kind"])
    use_compile_cache()
    accelerator(cell.chips)
    dep = harness.Deployment(cell, 7)
    with tempfile.TemporaryDirectory() as tmp:
        harness.window(dep, 1.0, loops.Profiler(tmp, 0.25, 0.5))
        src = sorted(Path(tmp).rglob("*.xplane.pb"))[-1]
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, out / "resnet8_stream.xplane.pb")
        print(out / "resnet8_stream.xplane.pb", src.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
