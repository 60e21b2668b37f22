"""step_mfu.<mix>: int8 operations (two per multiply-accumulate of every
conv and dense layer, from the shapes) of the frames answered per second
outside the profiled part of the window, as a share of the chips' int8
peak."""

from bench import ops
from bench.metrics import _untraced


def read(run):
    rate = _untraced.rate(run)
    if rate is None:
        return None
    return 100.0 * ops.frame_ops(run.cell.layers) * rate / (
        run.peaks["int8_ops"] * run.cell.chips)
