"""conv_roofline.<mix>: the least time the calls answered in the profiled
window need for their convolutions, over the device time of the operations
that computed them (``bench/conv_ops.json``).

The least time of one call is, layer by layer, the larger of its int8
operations over the int8 peak and its int8 bytes (activations in and out
per frame, weights once per call) over the memory bandwidth;
``bench.ops.least_seconds`` says which bound sets how much of it.
"""

from bench import ops


def read(run):
    t = run.trace
    if not t or not t["fetches"] or t["conv_s"] <= 0:
        return None
    least = ops.least_seconds(run.cell.layers, run.cell.batch,
                              run.peaks["int8_ops"],
                              run.peaks["hbm_bytes_per_s"])["seconds"]
    return 100.0 * least * t["fetches"] / t["conv_s"]
