"""Operation and byte counts against hand counts."""

import json
from pathlib import Path

import pytest

from bench import ops
from bench.models import resnet

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,macs,n_conv", [
    ("resnet18_cifar", 139_299_328, 20),
    ("resnet8", 12_501_632, 9),
])
def test_frame_macs_match_hand_counts(name, macs, n_conv):
    layers = resnet.layers(cfg(name))
    assert ops.frame_macs(layers) == macs
    assert ops.frame_ops(layers) == 2 * macs
    assert sum(layer["kind"] == "conv" for layer in layers) == n_conv


def test_stem_counts_by_hand():
    stem = resnet.layers(cfg("resnet18_cifar"))[0]
    # 32x32 outputs, 3x3 taps, 3 -> 32 channels
    assert ops.macs(stem) == 32 * 32 * 9 * 3 * 32
    assert ops.weight_bytes(stem) == 9 * 3 * 32
    assert ops.act_bytes(stem) == 32 * 32 * 3 + 32 * 32 * 32


def test_least_seconds_takes_the_larger_bound():
    conv = {"kind": "conv", "k": 3, "cin": 256, "cout": 256,
            "in_hw": (4, 4), "out_hw": (4, 4)}
    macs = 16 * 9 * 256 * 256
    # compute bound: 1 op/s of bandwidth to spare
    got = ops.least_seconds([conv], 2, peak_ops=1.0, peak_bytes_per_s=1e30)
    assert got["seconds"] == pytest.approx(2 * macs * 2)
    assert got["memory_bound_s"] == 0
    # memory bound: weights once, activations per frame
    got = ops.least_seconds([conv], 2, peak_ops=1e30, peak_bytes_per_s=1.0)
    assert got["seconds"] == pytest.approx(2 * (2 * 16 * 256) + 9 * 256 * 256)
    assert got["compute_bound_s"] == 0


def test_dense_layers_are_left_out_of_conv_least_time():
    layers = resnet.layers(cfg("resnet8"))
    fc_only = [layer for layer in layers if layer["kind"] == "fc"]
    assert ops.least_seconds(fc_only, 4, 1.0, 1.0)["seconds"] == 0
