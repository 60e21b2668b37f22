"""int8_roofline.<mix>: the least time the calls answered in the profiled
window need for their int8 convolutions, dense and grouped, over the device
time of the operations that computed them (``bench/conv_ops.json``, the
denominator of ``conv_roofline``).

Each conv is counted per group, as its int8 deployment computes it: a
conv of ``groups`` groups reads ``cin / groups`` input channels for each
output channel, so one frame takes ``2 · ho · wo · k² · (cin / groups) ·
cout`` operations and ``hi · wi · cin + ho · wo · cout`` bytes of
activations, and its weights ``k² · (cin / groups) · cout`` bytes once a
call.  A layer's least time is the larger of its operations over the int8
peak and its bytes over the memory bandwidth.  A layer without ``groups``
has one, and then the count is ``bench.ops.least_seconds``'s.

The denominator is every fusion the device trace counts as a conv: where
XLA fuses a float product of two activations (YOLO11n's attention) as a
``kOutput`` fusion it counts there, with no operations in the numerator,
as ResNet's dense dot already does for ``conv_roofline``.
"""

from typing import Dict, List


def least_seconds(layers: List[Dict], batch: int, peak_ops: float,
                  peak_bytes_per_s: float) -> float:
    """The least time one call of ``batch`` frames can take on the conv
    layers of ``layers``, counted per group."""
    total = 0.0
    for layer in layers:
        if layer["kind"] != "conv":
            continue
        (hi, wi), (ho, wo) = layer["in_hw"], layer["out_hw"]
        weights = (layer["k"] ** 2 * layer["cin"] // layer.get("groups", 1)
                   * layer["cout"])
        ops = 2 * ho * wo * weights * batch
        acts = (hi * wi * layer["cin"] + ho * wo * layer["cout"]) * batch
        total += max(ops / peak_ops, (acts + weights) / peak_bytes_per_s)
    return total


def read(run):
    t = run.trace
    if not t or not t["fetches"] or t["conv_s"] <= 0:
        return None
    least = least_seconds(run.cell.layers, run.cell.batch,
                          run.peaks["int8_ops"], run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * t["fetches"] / t["conv_s"]
