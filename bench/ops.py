"""Operations and bytes of a CNN's convolution and dense layers, from shapes.

A layer is a dict with ``kind`` (``"conv"`` or ``"fc"``), ``k``, ``cin``,
``cout`` and the output map ``out_hw`` (``(1, 1)`` for a dense layer); the
model modules under ``bench/models`` list them.  Everything is counted as an
int8 deployment computes it: one multiply-accumulate is two operations, and
activations and weights take one byte each.  These counts, not the program's
own cost model, are what the benchmark's utilization and roofline shares
divide by.
"""

from __future__ import annotations

from typing import Dict, Iterable, List


def macs(layer: Dict) -> int:
    """Multiply-accumulates of one frame through ``layer``."""
    ho, wo = layer["out_hw"]
    return ho * wo * layer["k"] * layer["k"] * layer["cin"] * layer["cout"]


def weight_bytes(layer: Dict) -> int:
    """int8 weights of ``layer``, read once per call whatever the batch."""
    return layer["k"] * layer["k"] * layer["cin"] * layer["cout"]


def act_bytes(layer: Dict) -> int:
    """int8 input and output activations of one frame through ``layer``."""
    hi, wi = layer["in_hw"]
    ho, wo = layer["out_hw"]
    return hi * wi * layer["cin"] + ho * wo * layer["cout"]


def frame_macs(layers: Iterable[Dict], kinds=("conv", "fc")) -> int:
    return sum(macs(layer) for layer in layers if layer["kind"] in kinds)


def frame_ops(layers: Iterable[Dict], kinds=("conv", "fc")) -> int:
    """int8 operations of one frame: two per multiply-accumulate."""
    return 2 * frame_macs(layers, kinds)


def least_seconds(layers: List[Dict], batch: int, peak_ops: float,
                  peak_bytes_per_s: float, kinds=("conv",)) -> Dict:
    """The least time one call of ``batch`` frames can take on the chosen
    layers: the larger of its operations over the peak rate and its bytes
    over the memory bandwidth, added layer by layer.

    Returns the seconds and how many of them each bound set.
    """
    total = compute = memory = 0.0
    for layer in layers:
        if layer["kind"] not in kinds:
            continue
        t_ops = 2 * macs(layer) * batch / peak_ops
        t_mem = (act_bytes(layer) * batch + weight_bytes(layer)) / peak_bytes_per_s
        total += max(t_ops, t_mem)
        if t_ops >= t_mem:
            compute += t_ops
        else:
            memory += t_mem
    return {"seconds": total, "compute_bound_s": compute,
            "memory_bound_s": memory}
