"""Per-frame latency of a stream: from each frame's scheduled arrival to
its logits on the host, over every frame of the window (a failed frame
counts the time until it was given up)."""

import numpy as np


def percentile_ms(run, q):
    calls = [c for c in run.record.calls if "due" in c]
    if not calls:
        return None
    lat = np.array([c["done"] - c["due"] for c in calls])
    return float(np.percentile(lat, q)) * 1e3
