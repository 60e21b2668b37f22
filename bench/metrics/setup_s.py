"""setup_s: process start to the first timed frame: imports, backend start,
weights, frames, placement, calibration and warm-up (host clock)."""


def read(run):
    return run.setup_s
