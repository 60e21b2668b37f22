"""Run one cell of the benchmark and print its result as one JSON line.

    python3 bench/run.py --workload resnet18_cifar.offline --seed 1 \\
        --seconds 30 --trace 0

Run from the root of a checkout, on a machine with the chips the cell asks
for.  With ``--trace 0`` the result holds the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
part of the window.  The numbers that decide ``correct`` are printed beside
their limits as the last lines of standard error and under ``check``.
Without an accelerator, or with fewer chips than the cell asks for, the run
exits with code 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

PEAKS = ROOT / "bench" / "peaks.json"
CACHE_DIR = ROOT / ".jax_cache"     # fixed: the path is part of the cache key


class NoAccelerator(RuntimeError):
    pass


def accelerator(chips: int):
    """The devices to measure on; raises where JAX found no accelerator,
    fewer than ``chips`` of them, or a kind with no entry in the peaks."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoAccelerator(
            f"JAX found no accelerator (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    table = json.loads(PEAKS.read_text())["devices"]
    kind = devices[0].device_kind
    if kind not in table:
        raise NoAccelerator(f"no peaks for device kind {kind!r} in {PEAKS}")
    return devices, table[kind]


def use_compile_cache() -> None:
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.Cell(args.workload)
    use_compile_cache()
    try:
        devices, peaks = accelerator(cell.chips)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              T_START, devices, peaks)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
