"""idle_share.<mix>: share of the time in which no operation runs on the
device, at the frame rate of the untraced part of the window: 1 - device
busy time per frame (the union of the operations' intervals in the
profiled part, over the frames answered there) x untraced frames/s.

The profiler slows the host and so stretches the gaps it records; the
busy time of a frame is the device's and does not stretch.
"""

from bench.metrics import _untraced


def read(run):
    t = run.trace
    rate = _untraced.rate(run)
    if not t or not t["fetches"] or rate is None:
        return None
    per_frame = t["busy_s"] / (t["fetches"] * run.cell.batch)
    return 100.0 * (1.0 - per_frame * rate)
