"""The YOLO11n cell on the CPU at 64x64: its configuration, its reference,
a run that is correct, a classifier broken under boxes that pass, and the
per-group roofline count."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import ROOT, cpu_run, small_cell

from bench import check, harness, ops
from bench.models import yolo11n, yolov8n

CELL = "yolo11n.stream"
HW = [64, 64]


def small(frames=8):
    """The cell at 64x64, 8 frames in the pool, all of them compared."""
    cell = small_cell(CELL, frames=frames)
    cell.cfg = dict(cell.cfg, image_hw=HW)
    cell.layers = cell.model.layers(cell.cfg)
    return cell


def config():
    return json.loads((ROOT / "bench" / "configs" / "yolo11n.json").read_text())


def test_published_sizes():
    # Ultralytics: 2.6 M parameters and 6.5 GFLOPs (2 x MACs) for YOLO11n
    cfg = config()
    layers = yolo11n.layers(cfg)
    assert len(layers) == 87
    depthwise = [c for c in layers if c["groups"] != 1]
    assert len(depthwise) == 7
    assert all(c["groups"] == c["cin"] == c["cout"] and c["k"] == 3
               for c in depthwise)
    assert sorted(c["cin"] for c in depthwise) == [64, 80, 80, 80, 128, 128,
                                                   256]
    macs = sum(c["out_hw"][0] * c["out_hw"][1] * c["k"] ** 2
               * c["cin"] // c["groups"] * c["cout"] for c in layers)
    assert macs == 3_239_993_600
    n = sum(c["k"] ** 2 * c["cin"] // c["groups"] * c["cout"] + c["cout"]
            for c in layers)
    assert n == 2_616_232
    assert {c["name"] for c in layers} >= {
        "b10.m.0.attn.qkv", "b10.m.0.attn.pe", "b10.m.0.attn.proj",
        "b6.m.0.m.1.cv2", "head.cv3.0.1.0"}
    assert cfg["check"]["parts"] == {"box": [0, 4], "cls": [4, 84]}
    assert cfg["reduced"] == []


def test_reference_matches_the_program_in_float():
    # the benchmark's own YOLO11n and the program's yolo11.forward, written
    # apart, agree on the same weights within float32 rounding
    from repro.models.cnn import yolo11
    cfg = dict(config(), image_hw=HW)
    params = yolo11n.init_params(jax.random.key(3), cfg)
    x = jax.random.normal(jax.random.key(4), (3, 64, 64, 3))
    ref = np.asarray(yolo11n.reference_fn(cfg)(params, x))
    assert ref.shape == (3, 8 * 8 + 4 * 4 + 2 * 2, 84)
    np.testing.assert_allclose(ref, np.asarray(yolo11.forward(params, x)),
                               rtol=1e-5, atol=1e-4)


def test_cpu_run_is_correct():
    res = cpu_run(small(), seed=2**31 + 77)
    assert res["correct"] is True, res["check"]
    assert set(res["check"]) == {"box.rel_l2", "box.worst_frame",
                                 "cls.rel_l2", "cls.worst_frame", "failed",
                                 "lowered_in_window"}
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"p50_ms", "p90_ms", "setup_s"}


def test_dead_classifier_fails_on_its_part_alone(monkeypatch):
    deploy = yolo11n.deploy

    def faulty_deploy(*args, **kwargs):
        serve = deploy(*args, **kwargs)
        return lambda frames: serve(frames).at[..., 4:].set(0.5)

    monkeypatch.setattr(yolo11n, "deploy", faulty_deploy)
    res = cpu_run(small(), seed=11)
    table = res["check"]
    assert res["correct"] is False
    for n in ("box.rel_l2", "box.worst_frame"):
        assert table[n]["value"] <= table[n]["limit"], (n, table)
    assert table["cls.rel_l2"]["value"] > table["cls.rel_l2"]["limit"], table


def test_control_fails_its_limits():
    cell = small()
    cfg = cell.cfg
    params = yolo11n.init_params(jax.random.key(5), cfg)
    g = np.random.default_rng(5)
    calib = jnp.asarray(g.standard_normal((16, 64, 64, 3), np.float32))
    x = g.standard_normal((8, 64, 64, 3), np.float32)
    want = check.in_blocks(
        lambda b: yolo11n.reference_fn(cfg)(params, b), x)
    got = check.in_blocks(
        lambda b: yolo11n.control_fn(cfg, cfg["check"]["control_qmax"])(
            params, calib, b), x)
    verdict = check.verdict(check.numbers(got, want, cfg["check"]["parts"]),
                            cfg["check"]["limits"])
    assert verdict["correct"] is False


def test_int8_roofline_counts_dense_layers_as_conv_roofline():
    # every layer of YOLOv8n has one group: the per-group count is
    # ops.least_seconds's, at the batch and peaks of any call
    reader = harness.reader("int8_roofline.stream")
    count = reader.__globals__["least_seconds"]
    layers = yolov8n.layers(json.loads(
        (ROOT / "bench" / "configs" / "yolov8n.json").read_text()))
    for batch in (1, 4):
        want = ops.least_seconds(layers, batch, 393e12, 819e9)["seconds"]
        assert count(layers, batch, 393e12, 819e9) == pytest.approx(
            want, rel=1e-12)
    # grouped, a conv reads cin / groups channels per output: fewer ops and
    # weight bytes than the dense conv of the same shapes, the same maps
    dense = {"kind": "conv", "k": 3, "cin": 80, "cout": 80,
             "in_hw": (80, 80), "out_hw": (80, 80)}
    dw = dict(dense, groups=80)
    assert count([dw], 1, 393e12, 819e9) == pytest.approx(
        (80 * 80 * 80 * 2 + 9 * 80) / 819e9)
    assert count([dense], 1, 393e12, 819e9) == pytest.approx(
        ops.least_seconds([dense], 1, 393e12, 819e9)["seconds"])


def test_int8_roofline_reads_the_summary():
    from test_metrics import make_run
    read = harness.reader("int8_roofline.stream")
    trace = {"busy_s": 0.5, "conv_s": 0.2, "fetches": 100, "window_s": 4.0}
    run = make_run([], trace=trace, batch=1)
    least = read.__globals__["least_seconds"](run.cell.layers, 1, 1e9, 1e9)
    assert read(run) == pytest.approx(100.0 * least * 100 / 0.2)
    assert read(make_run([], trace=None)) is None
    assert read(make_run([], trace=dict(trace, fetches=0))) is None
